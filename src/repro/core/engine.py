"""Stacked-client simulation engine for (decentralized) federated learning.

The engine is a **composable round program** (``repro.core.program``): one
algorithm = a (LocalSolver, Compressor, Mixer) stage composition from
``repro.core.stages`` over the flat ``(n_clients, D)`` client-parameter
bank, so one round is exactly the paper's two dense primitives — a single
column-stochastic gossip matmul ``X' = P @ X`` over the whole model and one
fused momentum/descent/de-bias elementwise pass — both dispatched to the
Pallas kernels in ``repro.kernels`` (interpret mode on CPU, Mosaic on TPU).

``AlgoConfig`` is the declarative point in that composition space and
``ALGORITHMS`` expresses Algorithm 1 (DFedSGPSM, the flagship), all seven
paper baselines, and the DFedSGPM ablation as registry compositions.
:class:`FLTrainer` is a thin stateful wrapper over the pure
``program.init``/``program.step`` core; the seed per-leaf pytree path is
retained (``flat=False``) as the equivalence oracle and benchmark baseline.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import pushsum, topology
from repro.core.program import FLState, RoundProgram, make_program
from repro.core.stages import _sample_batch
from repro.core.sam import (
    apply_update,
    momentum_update,
    sam_gradient,
)

__all__ = [
    "AlgoConfig",
    "ALGORITHMS",
    "FLState",
    "FLTrainer",
    "RoundProgram",
    "make_algo",
    "make_program",
]


@dataclasses.dataclass(frozen=True)
class AlgoConfig:
    """One federated-optimization algorithm = one stage composition.

    ``solver`` / ``compressor`` / ``comm`` name entries in the
    ``repro.core.stages`` registries (``comm`` selects the mixer:
    directed | symmetric | central); the scalar fields are the stage
    hyperparameters.
    """

    name: str = "dfedsgpsm"
    comm: str = "directed"  # mixer: directed | symmetric | central
    local_steps: int = 5
    rho: float = 0.0  # SAM perturbation radius (0 = off)
    alpha: float = 0.0  # local momentum coefficient (0 = off)
    selection: bool = False  # DFedSGPSM-S neighbor selection
    lr: float = 0.1
    lr_decay: float = 0.998
    batch_size: int = 32
    solver: str = "sam_momentum"  # sam_momentum | sgd | proximal
    compressor: str = "identity"  # identity | int8_rows | topk_ef
    topk_ratio: float = 0.05  # kept fraction per row (topk_ef)
    prox_mu: float = 0.01  # proximal pull strength (proximal solver)
    # Legacy spelling of ``compressor="int8_rows"`` (kept for the seed
    # pytree path, which quantizes per-leaf instead of per-row).
    quantize_gossip: bool = False


ALGORITHMS: dict[str, AlgoConfig] = {
    "fedavg": AlgoConfig("fedavg", "central"),
    "dpsgd": AlgoConfig("dpsgd", "symmetric", local_steps=1),
    "dfedavg": AlgoConfig("dfedavg", "symmetric"),
    "dfedavgm": AlgoConfig("dfedavgm", "symmetric", alpha=0.9),
    "dfedsam": AlgoConfig("dfedsam", "symmetric", rho=0.25),
    "sgp": AlgoConfig("sgp", "directed", local_steps=1),
    "osgp": AlgoConfig("osgp", "directed"),
    "dfedsgpm": AlgoConfig("dfedsgpm", "directed", alpha=0.9),
    "dfedsgpsm": AlgoConfig("dfedsgpsm", "directed", alpha=0.9, rho=0.1),
    "dfedsgpsm_s": AlgoConfig(
        "dfedsgpsm_s", "directed", alpha=0.9, rho=0.1, selection=True
    ),
}


def make_algo(name: str, **overrides) -> AlgoConfig:
    return dataclasses.replace(ALGORITHMS[name], **overrides)


def _quantize_dequantize(tree):
    """Simulated int8 symmetric quantization of gossip payloads (per-leaf
    global scale; the flat bank uses the tighter per-row Int8RowCompressor)."""

    def qdq(x):
        flat_x = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(flat_x)) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(flat_x / scale), -127, 127)
        return (q * scale).astype(x.dtype)

    return jax.tree.map(qdq, tree)


class FLTrainer:
    """Thin stateful wrapper over the pure round program.

    Args:
      loss_fn: ``loss_fn(params, batch) -> (loss, accuracy)``.
      init_fn: ``init_fn(key) -> params`` for a single client.
      client_data: pytree whose leaves have leading dims (n_clients, m, ...).
      algo: AlgoConfig (a stage composition).
      topo: TopologyConfig (ignored for centralized algorithms).
      flat: run rounds on the flat (n, D) bank through the Pallas kernels
        (default); ``False`` selects the seed per-leaf pytree path, kept as
        the kernel-free equivalence oracle.
      gossip: mixing-operator representation — ``"auto"`` (density rule:
        neighbor-list sparse gossip once n is large and k_max/n small),
        or force ``"sparse"`` / ``"dense"``.
      link: unreliable-link scenario (``topology.LinkModel``): per-round
        edge drops (exactly column-stochastic after renormalization),
        bounded delivery delays, or event-triggered transmission.  ``None``
        (default) or an all-zero model is bitwise the perfect-link round.
      churn: node-failure scenario (``topology.ChurnModel``): whole
        clients crash and (optionally) rejoin per round; dead nodes leave
        the sampled operator wholesale and their push-sum mass freezes on
        the self-loop, keeping live + in-flight + frozen mass == n
        exactly.  Composes with ``link`` drops and delays.  ``None``
        (default) or an all-zero model is bitwise the immortal round.
      paged: virtual client population — the full (n, D) bank lives in a
        disk-backed :class:`repro.store.ClientStore` under ``store_dir``
        and each round pages in only its fault-in closure (the ``k_active``
        sampled clients plus their in-neighbors), with background prefetch
        and async write-back.  Device/host buffers scale with the closure,
        not n; the checkpoint is the store itself.  Directed push-sum,
        perfect links, single host only.
      delta: low-rank delta bank (``repro.core.DeltaConfig``, or just a
        rank / ``"full"``): clients share a frozen base model and bank
        rows hold only adapter payloads — ``(A, B)`` factors per selected
        2-D leaf, dense deltas for small leaves — so every bank consumer
        (gossip, EF residuals, link buffers, the paged store) shrinks from
        D to d_delta.  ``rank="full"`` reproduces the dense bank to float
        tolerance (the equivalence oracle).
      bank_dtype: storage dtype of the bank rows (e.g. ``jnp.bfloat16``);
        momentum and EF residuals stay float32, so error feedback remains
        exact.

    ``fit`` drives ``program.run_superstep`` — jit-resident supersteps of
    rounds with in-scan eval — and returns per-round history records; for
    the stacked device-side history or custom schedules use
    ``self.program`` (or ``repro.core.make_program``) directly.
    """

    def __init__(
        self,
        loss_fn: Callable,
        init_fn: Callable,
        client_data,
        algo: AlgoConfig,
        topo: topology.TopologyConfig,
        seed: int = 0,
        participation: float = 0.1,
        flat: bool = True,
        gossip: str = "auto",
        link: topology.LinkModel | None = None,
        churn: topology.ChurnModel | None = None,
        mesh=None,
        paged: bool = False,
        store_dir: str | None = None,
        k_active: int = 0,
        rows_per_chunk: int = 256,
        prefetch: bool = True,
        lru_rows: int | None = None,
        faults=None,
        delta=None,
        bank_dtype=None,
    ):
        if paged:
            if not flat:
                raise ValueError("paged training runs on the flat bank")
            if mesh is not None:
                raise ValueError("paged training is single-host; drop the "
                                 "mesh (disk, not devices, bounds n)")
            if link is not None and link.active:
                raise ValueError("paged training models perfect links only")
            if not store_dir:
                raise ValueError("paged=True needs store_dir")
            if k_active < 1:
                raise ValueError("paged=True needs k_active >= 1")
        elif faults is not None:
            raise ValueError(
                "faults= injects into the disk-backed store; it needs "
                "paged=True"
            )
        if not flat and mesh is not None:
            raise ValueError("the flat=False oracle path is single-device")
        if not flat and (delta is not None or bank_dtype is not None):
            raise ValueError(
                "the flat=False oracle path keeps full-precision per-leaf "
                "pytrees; delta=/bank_dtype= need the flat bank"
            )
        if not flat and link is not None and link.active:
            # The oracle predates the link subsystem; silently ignoring the
            # scenario would invalidate it as an equivalence baseline.
            raise ValueError(
                "the flat=False oracle path models perfect links only"
            )
        if not flat and churn is not None and churn.active:
            raise ValueError(
                "the flat=False oracle path models an immortal population "
                "only"
            )
        if not flat and (
            algo.solver != "sam_momentum"
            or algo.compressor not in ("identity", "int8_rows")
        ):
            # The oracle implements exactly the paper compositions; silently
            # running a different algorithm than the flat path would defeat
            # its purpose as the equivalence baseline.
            raise ValueError(
                "the flat=False oracle path only supports the "
                "sam_momentum solver with identity/int8_rows compression, "
                f"not solver={algo.solver!r} compressor={algo.compressor!r}"
            )
        self.loss_fn = loss_fn
        self.init_fn = init_fn
        self.data = client_data
        self.algo = algo
        self.topo = topo
        self.participation = participation
        self.flat = flat
        self.n = topo.n_clients
        # Paged mode drives churn host-side in the runner (dead clients
        # leave the sampling pool; the program itself stays churn-free).
        self.program = make_program(
            loss_fn, init_fn, client_data, algo, topo, participation,
            gossip=gossip, link=link,
            churn=None if paged else churn,
            mesh=mesh, delta=delta, bank_dtype=bank_dtype,
        )
        self.spec = self.program.spec
        self._exp_cycle = self.program.exp_cycle
        self.paged = paged
        self.runner = None
        # Rounds run through fit's supersteps: the profiler's step number.
        self._fit_rounds = 0

        key = jax.random.PRNGKey(seed)
        if paged:
            # The bank never materializes: the store holds the population,
            # the runner pages closures through program.step_active.
            from repro.store import PagedRunner

            self.runner = PagedRunner(
                self.program, store_dir, k_active, seed=seed,
                rows_per_chunk=rows_per_chunk, prefetch=prefetch,
                lru_rows=lru_rows, churn=churn, faults=faults,
            )
            self.state = None
            self._round_jit = None
        elif flat:
            self.state = self.program.init(key)
            # Donate the state: the (n, D) banks are updated in place across
            # rounds instead of reallocating ~2 model copies per round.  The
            # client data rides as an argument, not an executable constant.
            step = jax.jit(self.program.step, donate_argnums=0)
            self._round_jit = lambda s: step(s, self.program.data)
        else:
            pkey, skey = jax.random.split(key)
            params0 = init_fn(pkey)
            w0 = jnp.ones((self.n,), jnp.float32)
            losses0 = jnp.zeros((self.n,), jnp.float32)
            if algo.comm == "central":
                self.state = FLState(
                    params0, None, w0, skey, jnp.int32(0), losses0
                )
            else:
                stacked = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (self.n,) + x.shape), params0
                )
                self.state = FLState(
                    stacked, None, w0, skey, jnp.int32(0), losses0
                )
            self._round_jit = jax.jit(self._round_legacy, donate_argnums=0)

        # Flat path: evaluate() compiles program.make_eval_fn — the same
        # masked fixed-shape eval run_superstep uses in-scan, so the two
        # can never drift numerically.  Entries hold a strong test_data
        # reference so the id() key cannot alias a freed dict.
        self._eval_cache: dict = {}

        # Legacy (flat=False) path: per-chunk masked eval over the pytree
        # params.  Every chunk is padded to the same batch size, so this
        # compiles once per trainer and never re-traces on the ragged final
        # chunk.  Per-example metrics are vmapped so the pad rows can be
        # masked out of the sums exactly.
        def _masked_eval(params, chunk, mask):
            def one(ex):
                return self.loss_fn(
                    params, jax.tree.map(lambda v: v[None], ex)
                )

            per_l, per_a = jax.vmap(one)(chunk)
            # where, not multiply: a non-finite loss on a zero pad row
            # (user loss_fns may divide by input norms) must not poison
            # the masked sum via NaN * 0.
            return (jnp.sum(jnp.where(mask, per_l, 0.0)),
                    jnp.sum(jnp.where(mask, per_a, 0.0)))

        self._eval_jit = jax.jit(_masked_eval)

    # -- legacy per-leaf pytree path (equivalence oracle) -------------------

    def _local_update(self, params_i, w_i, key_i, data_i, lr):
        """K iterations of Algorithm 1 lines 4-11 for one client."""
        algo = self.algo
        v0 = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), params_i)

        def step(carry, _):
            x, v, key = carry
            key, bk = jax.random.split(key)
            batch = _sample_batch(data_i, bk, algo.batch_size)
            z = jax.tree.map(lambda p: p / w_i, x)  # line 5: de-bias
            g, (loss, acc) = sam_gradient(self.loss_fn, z, batch, algo.rho)  # 6-8
            v = momentum_update(v, g, algo.alpha)  # line 9
            x = apply_update(x, v, lr)  # line 10
            return (x, v, key), (loss, acc)

        (x, _, _), (losses, accs) = jax.lax.scan(
            step, (params_i, v0, key_i), None, length=algo.local_steps
        )
        return x, losses.mean(), accs.mean()

    def _round_legacy(self, state: FLState):
        algo = self.algo
        lr = algo.lr * algo.lr_decay ** state.round.astype(jnp.float32)
        keys = jax.random.split(state.key, 2 + self.n)
        key, tkey, ckeys = keys[0], keys[1], keys[2:]

        if algo.comm == "central":
            return self._fedavg_round_legacy(state, lr, key, tkey, ckeys)

        x_half, losses, accs = jax.vmap(
            self._local_update, in_axes=(0, 0, 0, 0, None)
        )(state.params, state.w, ckeys, self.data, lr)

        x_send = x_half
        if algo.quantize_gossip or algo.compressor == "int8_rows":
            x_send = _quantize_dequantize(x_half)

        P = self._mixing(tkey, state)
        # The oracle path stays off-kernel by construction — it is what the
        # kernel-backed flat path is validated against.
        x_new = pushsum.gossip(P, x_send, use_kernel=False)
        if x_send is not x_half:
            # Same compressed-gossip semantics as the flat path: the
            # self-loop P[ii]·x_i is local memory and is never quantized.
            from repro.core.stages import _self_weights

            s = _self_weights(P)

            def fresh_self(xn, xh, xq):
                shape = (xn.shape[0],) + (1,) * (xn.ndim - 1)
                return xn + (s.reshape(shape) * (xh - xq)).astype(xn.dtype)

            x_new = jax.tree.map(fresh_self, x_new, x_half, x_send)
        w_new = (
            pushsum.gossip_weights(P, state.w)
            if algo.comm == "directed"
            else state.w
        )
        new_state = FLState(x_new, None, w_new, key, state.round + 1, losses)
        return new_state, {"loss": losses.mean(), "acc": accs.mean()}

    def _fedavg_round_legacy(self, state, lr, key, tkey, ckeys):
        m = max(int(self.participation * self.n), 1)
        sel = jax.random.permutation(tkey, self.n)[:m]

        def client(i, k):
            data_i = jax.tree.map(lambda d: d[i], self.data)
            return self._local_update(
                state.params, jnp.float32(1.0), k, data_i, lr
            )

        xs, losses, accs = jax.vmap(client)(sel, ckeys[:m])
        new_params = jax.tree.map(lambda s: s.mean(axis=0), xs)
        # Refresh the sampled clients' loss slots (parity with the flat
        # central step — the vector rides checkpoints and selection).
        new_state = FLState(
            new_params, state.mom, state.w, key, state.round + 1,
            state.losses.at[sel].set(losses)
        )
        return new_state, {"loss": losses.mean(), "acc": accs.mean()}

    # -- mixing-matrix selection (delegates to the program) -----------------

    def _mixing(self, tkey, state: FLState):
        return self.program.mixing_matrix(tkey, state)

    # -- public API ----------------------------------------------------------

    def run_round(self):
        if self.paged:
            return self.runner.run_round()
        self.state, metrics = self._round_jit(self.state)
        return metrics

    def average_model(self):
        """Consensus model x̄ (Algorithm 1 output)."""
        if self.paged:
            # Streamed over store chunks; (n, D) never materializes.
            return self.spec.unravel(jnp.asarray(self.runner.mean_params()))
        if self.algo.comm == "central":
            if self.flat:
                return self.spec.unravel(self.state.params)
            return self.state.params
        if self.flat:
            return self.spec.unravel(self.state.params.mean(axis=0))
        return jax.tree.map(lambda x: x.mean(axis=0), self.state.params)

    def debiased_models(self):
        if self.paged:
            raise ValueError(
                "debiased_models materializes the full (n, D) bank — the "
                "point of paged mode is that it never exists; stream rows "
                "via trainer.runner.store.iter_chunks() instead"
            )
        if self.flat and self.algo.comm != "central":
            from repro.core.flat import BoundDeltaSpec

            if isinstance(self.spec, BoundDeltaSpec):
                # Delta rows de-bias through the spec: z_i = base +
                # expand(row_i) / w_i (the dense-row division would divide
                # the frozen base by w too).
                return self.spec.debias_stacked(
                    self.state.params, self.state.w
                )
            z = pushsum.debias_bank(self.state.params, self.state.w)
            return self.spec.unravel_stacked(z)
        return pushsum.debias(self.state.params, self.state.w)

    def consensus_error(self):
        """Mean squared distance of de-biased params from the average."""
        if self.paged:
            return self.runner.consensus_error()
        if self.flat and self.algo.comm != "central":
            return pushsum.consensus_error_bank(self.state.params, self.state.w)
        return pushsum.consensus_error(self.state.params, self.state.w)

    def evaluate(self, test_data, batch: int = 1024):
        if self.flat and not self.paged:
            # Exactly the in-scan eval of run_superstep, jitted standalone.
            key = (id(test_data), batch)
            entry = self._eval_cache.get(key)
            if entry is None:
                entry = (
                    jax.jit(self.program.make_eval_fn(test_data, batch)),
                    test_data,
                )
                self._eval_cache[key] = entry
            tl, ta = entry[0](self.state)
            return float(tl), float(ta)
        params = self.average_model()
        n = test_data["x"].shape[0]
        tot_l, tot_a = 0.0, 0.0
        for i in range(0, n, batch):
            chunk = {k: v[i : i + batch] for k, v in test_data.items()}
            b = chunk["x"].shape[0]
            if b < batch:  # pad to the fixed shape; the mask strips it
                chunk = {
                    k: jnp.concatenate(
                        [v, jnp.zeros((batch - b,) + v.shape[1:], v.dtype)]
                    )
                    for k, v in chunk.items()
                }
            mask = jnp.arange(batch) < b
            l, a = self._eval_jit(params, chunk, mask)
            tot_l += float(l)
            tot_a += float(a)
        return tot_l / n, tot_a / n

    def fit(
        self,
        rounds: int,
        test_data=None,
        eval_every: int = 0,
        log=None,
        superstep: int = 0,
    ):
        """Train ``rounds`` rounds and return the per-round history.

        On the flat path this drives ``program.run_superstep``: rounds are
        ``lax.scan``-ned inside one jit per superstep with donated carry and
        the eval runs *in-scan* at the ``eval_every`` cadence (keyed on the
        global round counter, so chunked supersteps and checkpoint resume
        keep the same schedule).  The host — history records and the ``log``
        callback — is only touched at superstep boundaries.

        Args:
          superstep: rounds per jit-resident scan chunk; ``0`` (default)
            runs all ``rounds`` as one superstep.  The ``flat=False`` oracle
            path keeps the per-round Python loop regardless.
        """
        if not self.flat or self.paged:
            # Paged rounds are host-orchestrated by design (the plan /
            # prefetch / write-back pipeline IS the host loop).
            return self._fit_python_loop(rounds, test_data, eval_every, log)
        history = []
        done = 0
        chunk = rounds if superstep <= 0 else superstep
        cadence = eval_every if test_data is not None else 0
        span = jax.profiler.TraceAnnotation
        while done < rounds:
            length = min(chunk, rounds - done)
            # Profiler spans on the superstep boundary: the step number is
            # the host's count of rounds fit has run, never a device read.
            with jax.profiler.StepTraceAnnotation(
                    "fl.superstep", step_num=self._fit_rounds):
                with span("fl.dispatch"):
                    self.state, hist = self.program.run_superstep(
                        self.state, length, cadence, test_data
                    )
                # ONE device->host transfer per superstep boundary;
                # indexing device arrays per round would re-introduce the
                # per-round syncs the scanned superstep exists to eliminate.
                with span("fl.fetch"):
                    hist = jax.device_get(hist)
                with span("fl.records"):
                    history += self._records(hist, done, length, log)
            done += length
            self._fit_rounds += length
        return history

    @staticmethod
    def _records(hist, done, length, log):
        """The per-round history records of one fetched superstep."""
        records = []
        evals = hist.get("eval_mask")
        for i in range(length):
            rec = {
                "round": done + i,
                "loss": float(hist["loss"][i]),
                "acc": float(hist["acc"][i]),
            }
            # Link-scenario extras: transmitted fraction (event-triggered
            # rounds) and the exact-mass invariant.
            for k in ("comm_fraction", "w_mass", "w_inflight"):
                if k in hist:
                    rec[k] = float(hist[k][i])
            if evals is not None and bool(evals[i]):
                rec["test_loss"] = float(hist["test_loss"][i])
                rec["test_acc"] = float(hist["test_acc"][i])
            records.append(rec)
            if log:
                log(rec)
        return records

    def _fit_python_loop(self, rounds, test_data, eval_every, log):
        """Per-round host loop — the ``flat=False`` oracle's and the paged
        runner's driver.  Paged trainers additionally stream a
        full-population eval (``PagedRunner.eval_population``) at the same
        cadence: cold chunks flow through ``store.iter_chunks`` so the
        record carries population metrics and their delta against the hot
        closure's view — eval breadth the closure alone cannot give."""
        history = []
        for r in range(rounds):
            metrics = self.run_round()
            rec = {"round": r, **{k: float(v) for k, v in metrics.items()}}
            if eval_every and (r + 1) % eval_every == 0:
                if test_data is not None:
                    tl, ta = self.evaluate(test_data)
                    rec.update(test_loss=tl, test_acc=ta)
                if self.paged:
                    rec.update(self.runner.eval_population(
                        closure_loss=metrics.get("loss")
                    ))
            history.append(rec)
            if log:
                log(rec)
        return history

    # -- checkpointing (full FLState) ---------------------------------------

    def save(self, directory: str | None = None, step: int = 0,
             keep: int = 3) -> str:
        """Checkpoint the full ``FLState`` (params + momentum bank +
        push-sum weights + round + key + compressor state).

        Paged trainers ignore ``directory``/``step``/``keep``: the
        checkpoint IS the store — ``save`` flushes dirty rows and commits
        ``(round, key)`` into the store manifest, returning the store path.
        """
        from repro import checkpoint

        if self.paged:
            return self.runner.save()
        if not self.flat:
            raise ValueError("full-state checkpointing needs the flat path")
        if directory is None:
            raise ValueError("save() needs a checkpoint directory")
        return checkpoint.save_state(
            directory, step, self.state, self.spec, keep=keep
        )

    def restore(self, path: str) -> FLState:
        """Warm-restart from a full-``FLState`` checkpoint (paged trainers
        re-sync to their store's last committed manifest)."""
        from repro import checkpoint

        if self.paged:
            self.runner.restore(path)
            return None
        if not self.flat:
            raise ValueError("full-state checkpointing needs the flat path")
        state = checkpoint.restore_state(path, self.spec)
        # Fail fast on compressor-state mismatch: a stateful compressor fed
        # an empty comp (or vice versa) would otherwise crash opaquely at
        # trace time inside the next round.
        needs = self.program.compressor.stateful
        has = not (isinstance(state.comp, tuple) and state.comp == ())
        if needs and not has:
            raise ValueError(
                f"{path} carries no compressor state, but "
                f"compressor={self.algo.compressor!r} needs its residual "
                "bank — it was saved from a stateless composition"
            )
        if has and not needs:
            raise ValueError(
                f"{path} carries compressor state, but this trainer's "
                f"compressor={self.algo.compressor!r} is stateless"
            )
        has_link = not (isinstance(state.link, tuple) and state.link == ())
        if self.program.linked != has_link:
            raise ValueError(
                f"{path} {'carries' if has_link else 'carries no'} "
                "unreliable-link state, but this trainer's link scenario "
                f"{'does not use' if has_link else 'needs'} it — restore "
                "with the composition that saved it"
            )
        if has_link:
            # Presence is not enough: a delayed carry restored into an
            # event-triggered program (or a different delay bound) would
            # crash opaquely inside the next traced round — compare the
            # buffer structure against what this mixer actually carries.
            want = self.program.mixer.link_buffers(state.params)
            for field in ("bufx", "bufw", "last"):
                have = getattr(state.link, field)
                exp = want.get(field)
                have_arr = not isinstance(have, tuple)
                if have_arr != (exp is not None) or (
                    have_arr and tuple(have.shape) != tuple(exp.shape)
                ):
                    raise ValueError(
                        f"{path} link carry field {field!r} is "
                        f"{tuple(have.shape) if have_arr else 'absent'}, "
                        "but this trainer's link composition expects "
                        f"{tuple(exp.shape) if exp is not None else 'none'}"
                        " — restore with the composition that saved it"
                    )
        has_churn = not (
            isinstance(state.churn, tuple) and state.churn == ()
        )
        if self.program.churned != has_churn:
            raise ValueError(
                f"{path} {'carries' if has_churn else 'carries no'} "
                "node-churn state, but this trainer's churn scenario "
                f"{'does not use' if has_churn else 'needs'} it — restore "
                "with the composition that saved it"
            )
        if has_churn:
            cold = self.program.churn_model.resurrect == "cold"
            has_tpl = not isinstance(state.churn.tpl, tuple)
            if cold != has_tpl:
                raise ValueError(
                    f"{path} churn carry "
                    f"{'holds' if has_tpl else 'holds no'} cold-"
                    "resurrection template row, but this trainer's "
                    f"ChurnModel.resurrect="
                    f"{self.program.churn_model.resurrect!r} — restore "
                    "with the composition that saved it"
                )
        # Re-place host-loaded leaves on the program mesh (identity when
        # unsharded) so a resumed run is row-sharded from its first round.
        self.state = self.program.shard_state(state)
        return self.state
