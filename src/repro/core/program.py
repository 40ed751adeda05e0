"""The composable round program: a pure ``init``/``step`` core over stages.

``make_program`` wires a (LocalSolver, Compressor, Mixer) composition —
usually resolved from an ``AlgoConfig`` via ``repro.core.stages`` — into a
:class:`RoundProgram` whose

    state           = program.init(key)          # FLState
    state, metrics  = program.step(state)        # one communication round
    state, history  = program.run(state, rounds) # lax.scan over step

are plain jittable functions of traced state only (topology, data, and the
stage composition are closed over as constants), optax-style.  Callers can
``jax.jit(program.step, donate_argnums=0)`` to update the (n, D) banks in
place, or scan whole training runs inside one jit.

``run_superstep`` is the production driver built on top: it jits one
``lax.scan`` over a whole *superstep* of rounds with donated carry and
performs the masked fixed-shape evaluation *in-scan* at the configured
cadence, so the host is only touched at superstep boundaries (checkpoint /
logging) — the Stochastic Gradient Push recipe for keeping the device,
not the Python loop, as the wall-clock ceiling.  ``FLTrainer`` in
``repro.core.engine`` is a thin stateful wrapper around exactly this API.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import topology
from repro.core.flat import (
    BankSpec,
    BoundDeltaSpec,
    DeltaConfig,
    bind_delta_spec,
    make_delta_spec,
    make_spec,
)
from repro.core.stages import (
    ChurnState,
    DelayedPushSumMixer,
    EventTriggeredMixer,
    IdentityCompressor,
    LinkState,
    comm_phase,
    make_stages,
)

__all__ = [
    "FLState",
    "ActiveSlots",
    "RoundProgram",
    "make_program",
    "plan_keys",
]


def plan_keys(key: jax.Array):
    """The paged round's PRNG chain: one split of the round key into
    ``(key_next, akey, tkey, ckey_base)`` — next round's key, the active-set
    permutation key, the topology pick key, and the base every client folds
    its global id into.  Host planner and the fully-resident reference
    driver both derive from exactly this chain, which is what makes
    paged == resident equivalence testable stream-for-stream."""
    ks = jax.random.split(key, 4)
    return ks[0], ks[1], ks[2], ks[3]


class ActiveSlots(NamedTuple):
    """Device-side view of one round's fault-in closure.

    ``ids[s]`` is the global client id resident in compact slot ``s``
    (layout ``[active | cold | pads]``; only the first ``k_active`` entries
    are read, for per-client PRNG folding).  ``idx``/``wgt`` are the
    compact-slot :class:`~repro.core.topology.NeighborList` of the
    closure-restricted mixing operator built by
    :func:`repro.store.paging.build_plan`."""

    ids: jnp.ndarray  # (c_max,) int32 global ids per resident slot
    idx: jnp.ndarray  # (c_max, 1 + k_in) int32 compact in-neighbor slots
    wgt: jnp.ndarray  # (c_max, 1 + k_in) float32 mixing weights


class FLState(NamedTuple):
    """Full round state — everything a warm restart needs."""

    params: Any  # flat (n, D) bank / (D,) central row; pytree when flat=False
    # End-of-round momentum bank, (n, D) float32 (None on the legacy path).
    # Algorithm 1 re-initializes v to zero each round, so training never
    # reads it back — it is carried for observability and checkpoint/warm-
    # restart of momentum-persistent variants.
    mom: Any
    w: jnp.ndarray  # (n,) push-sum weights (all-ones when unused)
    key: jax.Array
    round: jnp.ndarray  # int32 scalar
    losses: jnp.ndarray  # (n,) last local losses (drives selection)
    comp: Any = ()  # compressor state (e.g. error-feedback residual bank)
    # Unreliable-link carry (stages.LinkState): its own PRNG stream for
    # drop/delay draws plus the delayed in-flight payload buffers or the
    # event-trigger last-broadcast cache.  () on perfect-link programs.
    link: Any = ()
    # Node-churn carry (stages.ChurnState): its own PRNG stream plus the
    # (n,) liveness vector (and the cold-resurrection template row).
    # () on churn-free programs — immortal clients.
    churn: Any = ()


@dataclasses.dataclass(frozen=True)
class RoundProgram:
    """One federated-optimization algorithm as a stage composition.

    All fields are trace-time constants; ``init``/``step``/``run`` below are
    the only functions of traced values.
    """

    solver: Any
    compressor: Any
    mixer: Any
    loss_fn: Callable
    init_fn: Callable
    data: Any  # client-stacked pytree, leading dims (n_clients, m, ...)
    topo: topology.TopologyConfig
    spec: BankSpec
    n: int
    participation: float
    lr: float
    lr_decay: float
    selection: bool
    # Stack for time-varying exponential graphs: (hops, n, n) dense, or a
    # stacked (hops, n, 2) NeighborList on the sparse path.
    exp_cycle: Any
    # Mixing-operator representation: with sparse_mix the round samples
    # fixed-shape (n, k_max) neighbor lists and the whole push-sum step
    # (bank AND weight vector) runs O(n * k_max * D) without ever
    # materializing (n, n).  Resolved at build time by the density rule in
    # repro.kernels.ops.use_sparse_gossip (gossip="auto") or forced.
    gossip: str = "auto"
    sparse_mix: bool = False
    # Unreliable-link scenario (topology.LinkModel) — None models perfect
    # links and keeps the round bitwise identical to the pre-link program.
    # ``linked`` is the static routing flag: True when the link model is
    # active or the mixer carries link state, in which case the step
    # threads ``state.link`` and samples drops/delays from its key.
    link: Any = None
    linked: bool = False
    # Node-churn scenario (topology.ChurnModel) — None models immortal
    # clients and keeps the round bitwise identical to the pre-churn
    # program.  When set, the step threads ``state.churn`` (its own PRNG
    # stream + the (n,) liveness vector), masks dead nodes out of the
    # sampled operator before the link model's drops, and freezes their
    # mass on the self-loop so live + in-flight + frozen mass == n.
    churn_model: Any = None

    @property
    def churned(self) -> bool:
        return self.churn_model is not None
    # GSPMD row-sharded bank: a 1-D device mesh whose ``shard_axis`` names
    # the axis bank rows (params, momentum, EF residual, push-sum weights,
    # link carry) are partitioned along.  None keeps the single-device
    # program bitwise unchanged (all sharding constraints degrade to
    # identity).
    mesh: Any = None
    shard_axis: str = "clients"

    def __post_init__(self):
        # Per-program memo of compiled superstep drivers, keyed on the
        # (rounds, eval cadence, test-data identity) signature — repeated
        # supersteps of the same shape must hit the jit cache, not retrace.
        object.__setattr__(self, "_superstep_cache", {})
        from repro.launch.sharding import bank_row_pins

        pin, pin_link = bank_row_pins(self.mesh, self.shard_axis)
        object.__setattr__(self, "_pin", pin)
        object.__setattr__(self, "_pin_link", pin_link)

    # -- pure state constructor ---------------------------------------------

    def init_row(self, pkey: jax.Array) -> jnp.ndarray:
        """The broadcast initial bank row.  Dense bank: the ravelled
        ``init_fn(pkey)`` model.  Delta bank: the spec's init row (zero
        deltas over the frozen base; low-rank leaves LoRA-initialized) —
        every client starts at exactly the base model either way."""
        if isinstance(self.spec, BoundDeltaSpec):
            return self.spec.init_row(pkey)
        return self.spec.ravel(self.init_fn(pkey))

    def init(self, key: jax.Array) -> FLState:
        pkey, skey = jax.random.split(key)
        w0 = self.mixer.init_weights(self.n)
        losses0 = jnp.zeros((self.n,), jnp.float32)
        if self.mixer.kind == "central":
            row = self.spec.ravel(self.init_fn(pkey))
            return FLState(row, None, w0, skey, jnp.int32(0), losses0, ())
        row = self.init_row(pkey)
        bank = jnp.broadcast_to(row, (self.n, self.spec.dim))
        mom = jnp.zeros((self.n, self.spec.dim), jnp.float32)
        comp = self.compressor.init_state(self.n, self.spec.dim)
        link = ()
        if self.linked:
            # The link stream is folded off the seed key so the main
            # params/round stream stays exactly the perfect-link one.
            link = LinkState(
                key=jax.random.fold_in(key, 0x11AB),
                **self.mixer.link_buffers(bank),
            )
        churn = ()
        if self.churned:
            # Same isolation for the churn stream: folded off the seed,
            # never touching the main params/round chain.
            churn = ChurnState(
                key=jax.random.fold_in(key, 0x0C4B),
                live=jnp.full((self.n,), topology.LIVE, jnp.int8),
                tpl=(row if self.churn_model.resurrect == "cold" else ()),
            )
        return self.shard_state(
            FLState(bank, mom, w0, skey, jnp.int32(0), losses0, comp, link,
                    churn)
        )

    # -- GSPMD placement -----------------------------------------------------

    def shard_state(self, state: FLState) -> FLState:
        """Place every bank-row leaf of ``state`` on the ``shard_axis`` of
        the program mesh (scalars/keys replicated).  Identity without a
        mesh, so single-device callers — and ``init`` itself — compose
        through unconditionally.  ``engine.FLTrainer.restore`` routes
        host-loaded checkpoints through here so a resumed run is sharded
        from its first round."""
        if self.mesh is None or self.mixer.kind == "central":
            return state
        from jax.sharding import NamedSharding, PartitionSpec

        def _sh(lead, ndim):
            spec = [None] * ndim
            spec[lead] = self.shard_axis
            return NamedSharding(self.mesh, PartitionSpec(*spec))

        def row(x, lead=0):
            if x is None or isinstance(x, tuple):
                return x
            return jax.device_put(x, _sh(lead, x.ndim))

        def rep(x):
            return jax.device_put(
                x, NamedSharding(self.mesh, PartitionSpec())
            )

        link = state.link
        if link:
            link = link._replace(
                key=rep(link.key),
                bufx=row(link.bufx, 1),
                bufw=rep(link.bufw) if not isinstance(
                    link.bufw, tuple) else (),
                last=row(link.last),
            )
        churn = state.churn
        if churn:
            churn = churn._replace(
                key=rep(churn.key),
                live=row(churn.live),
                tpl=rep(churn.tpl) if not isinstance(
                    churn.tpl, tuple) else (),
            )
        return state._replace(
            params=row(state.params),
            mom=row(state.mom),
            w=row(state.w),
            key=rep(state.key),
            round=rep(state.round),
            losses=row(state.losses),
            comp=row(state.comp),
            link=link,
            churn=churn,
        )

    # -- mixing-matrix selection --------------------------------------------

    def mixing_matrix(self, tkey: jax.Array, state: FLState):
        # Every sampled family honors the configured ``topo.k_out`` —
        # ``participation`` only drives central (server) client sampling.
        # Returns the dense (n, n) matrix, or the fixed-shape NeighborList
        # when the density rule picked the sparse representation; every
        # downstream consumer (mixers, pushsum, kernels) dispatches on the
        # type.
        k_link = self.topo.k_out
        if self.sparse_mix:
            if self.mixer.kind == "symmetric":
                return topology.sample_symmetric_neighbors(
                    tkey, self.n, k_link
                )
            if self.selection:
                return topology.sample_kout_selective_neighbors(
                    tkey, state.losses, self.n, k_link
                )
            if self.exp_cycle is not None:
                hops = self.exp_cycle.idx.shape[0]
                t = jnp.mod(state.round, hops)
                return topology.NeighborList(
                    self.exp_cycle.idx[t], self.exp_cycle.wgt[t]
                )
            return topology.sample_neighbors(tkey, self.topo, t=0)
        if self.mixer.kind == "symmetric":
            return topology.sample_symmetric_k_regular(tkey, self.n, k_link)
        if self.selection:
            return topology.sample_kout_selective(
                tkey, state.losses, self.n, k_link
            )
        if self.exp_cycle is not None:
            # Time-varying exponential graph: round t uses cycle[t % hops].
            hops = self.exp_cycle.shape[0]
            return self.exp_cycle[jnp.mod(state.round, hops)]
        return topology.sample_mixing(tkey, self.topo, t=0)

    # -- one communication round --------------------------------------------

    def step(self, state: FLState, data=None):
        """One round.  ``data`` (default: the program's client data) lets a
        jitted driver pass the client data as an argument, so that it is
        not baked into the executable as a constant."""
        data = self.data if data is None else data
        lr = self.lr * self.lr_decay ** state.round.astype(jnp.float32)
        keys = jax.random.split(state.key, 2 + self.n)
        key, tkey, ckeys = keys[0], keys[1], keys[2:]
        if self.mixer.kind == "central":
            return self._central_step(state, lr, key, tkey, ckeys, data)

        # Node churn resolves FIRST: this round's liveness decides who
        # trains and whose edges survive.  A node down this round neither
        # trains nor communicates — its row and mass freeze on the
        # self-loop.  All branches are trace-time (self.churned is a
        # Python bool), so churn-free programs stay bitwise unchanged.
        alive = None
        params0, mom0, comp0 = state.params, state.mom, state.comp
        if self.churned:
            nkey, ckey = jax.random.split(state.churn.key)
            live_new = topology.churn_transition(
                ckey, state.churn.live, self.churn_model
            )
            alive = live_new == topology.LIVE
            if self.churn_model.resurrect == "cold":
                # A node rejoining this round restarts at the init
                # template in de-biased coordinates: x := w * template
                # keeps its frozen mass w bit-for-bit (the invariant),
                # while x/w == template exactly.  Momentum and any
                # compressor residual rows are zeroed with it.
                reborn = (
                    (state.churn.live == topology.DOWN)
                    & (live_new == topology.LIVE)
                )[:, None]
                params0 = jnp.where(
                    reborn,
                    (state.w[:, None] * state.churn.tpl).astype(
                        params0.dtype),
                    params0,
                )
                if mom0 is not None:
                    mom0 = jnp.where(reborn, 0.0, mom0)
                if not (isinstance(comp0, tuple) and comp0 == ()):
                    comp0 = jnp.where(reborn, 0.0, comp0)

        # Per-client PRNG rows and the solver outputs are pinned to the
        # bank's row sharding so the vmapped local phase stays shard-local.
        ckeys = self._pin(ckeys)
        X, V, losses, accs = self.solver.update(
            self.loss_fn, self.spec, params0, state.w, ckeys, data, lr
        )
        V = self._pin(V) if V is not None else V
        if self.churned:
            # Dead nodes did not train: their rows, momentum and last
            # losses carry through untouched (frozen).
            al = alive[:, None]
            X = jnp.where(al, X, params0)
            if V is not None:
                V = jnp.where(al, V, mom0)
            losses = jnp.where(alive, losses, state.losses)
        # The communication phase — compress, link drops/delays, mix — is
        # the shared ``stages.comm_phase`` (also driving the pod
        # ``round_step``): the compressor shapes what leaves each client
        # over the network while the self-loop contribution P[ii]·X[i]
        # stays full precision; with identity compression and no mesh the
        # phase is bitwise the pre-extraction inline sequence.  The graph
        # and the phase are the round's ``mix``, named for the profiler.
        with jax.named_scope("mix"):
            P = self.mixing_matrix(tkey, state)
            if self.churned:
                # Dead nodes leave the operator wholesale (in- AND
                # out-edges, masked before sender normalization); the link
                # model's per-edge drops then fail edges of the surviving
                # support.
                P = self.churn_model.mask_operator(
                    P, alive, symmetric=self.mixer.kind == "symmetric"
                )
            X, w_new, comp, link, extras = comm_phase(
                self.compressor, self.mixer, P, X, state.w, comp0,
                state.link,
                linked=self.linked, link_model=self.link,
                symmetric=self.mixer.kind == "symmetric",
                pin=self._pin, pin_link=self._pin_link,
                t=state.round,
            )
        churn = state.churn
        if self.churned:
            churn = ChurnState(nkey, live_new, state.churn.tpl)
        new_state = FLState(
            X, V, w_new, key, state.round + 1, losses, comp, link, churn
        )
        if self.churned:
            n_live = jnp.maximum(alive.sum(), 1).astype(jnp.float32)
            metrics = {
                "loss": jnp.where(alive, losses, 0.0).sum() / n_live,
                "acc": jnp.where(alive, accs, 0.0).sum() / n_live,
                **extras,
            }
            metrics["live_frac"] = alive.mean(dtype=jnp.float32)
            # Frozen mass parked on dead nodes' self-loops — the third
            # term of the exact invariant live + in-flight + frozen == n.
            metrics["dead_mass"] = jnp.where(alive, 0.0, w_new).sum()
        else:
            metrics = {"loss": losses.mean(), "acc": accs.mean(), **extras}
        if self.linked or self.churned:
            # Total push-sum mass, in-flight shares included — the exact
            # conservation invariant the link/churn subsystems are pinned
            # by (frozen dead mass stays in w, so it is already counted).
            inflight = (link.bufw.sum()
                        if self.linked and not isinstance(link.bufw, tuple)
                        else jnp.float32(0.0))
            metrics["w_mass"] = w_new.sum() + inflight
        return new_state, metrics

    def _central_step(self, state: FLState, lr, key, tkey, ckeys, data):
        m = max(int(self.participation * self.n), 1)
        sel = jax.random.permutation(tkey, self.n)[:m]
        data_sel = jax.tree.map(lambda d: d[sel], data)
        Xrep = jnp.broadcast_to(state.params, (m,) + state.params.shape)
        ones = jnp.ones((m,), jnp.float32)
        X, _, losses, accs = self.solver.update(
            self.loss_fn, self.spec, Xrep, ones, ckeys[:m], data_sel, lr
        )
        new_params = self.mixer.reduce(X)
        # The sampled clients' end-of-round losses refresh their slots in
        # the (n,) loss vector (it rides checkpoints and drives selection);
        # it used to be returned unchanged — zeros forever on this path.
        new_losses = state.losses.at[sel].set(losses)
        new_state = FLState(
            new_params, state.mom, state.w, key, state.round + 1,
            new_losses, state.comp, state.link
        )
        return new_state, {"loss": losses.mean(), "acc": accs.mean()}

    # -- one paged round on the compact resident bank -------------------------

    def step_active(
        self, state: FLState, slots: ActiveSlots, data_active, *,
        k_active: int,
    ):
        """One communication round over a **compact** ``(c_max, D)`` bank —
        the paged twin of :meth:`step` for partial participation.

        ``state`` here is the *resident* state: every bank leaf holds only
        the round's fault-in closure (layout ``[active | cold | pads]``,
        see :mod:`repro.store.paging`), ``state.key`` is the round's
        ``ckey_base`` from :func:`plan_keys` (the paged key chain lives on
        the host), and ``state.link`` is ``()`` — link scenarios are not
        paged.  Only the first ``k_active`` rows train locally; the mix
        runs the same :func:`~repro.core.stages.comm_phase` over the
        slot-remapped NeighborList in ``slots``, so compressors (including
        stateful EF residuals, resident like every other bank leaf) and the
        full-precision self-loop rule compose unchanged.  ``k_active`` is
        static: jit with ``static_argnames=("k_active",)``.
        """
        lr = self.lr * self.lr_decay ** state.round.astype(jnp.float32)
        ckeys = jax.vmap(
            lambda i: jax.random.fold_in(state.key, i)
        )(slots.ids[:k_active])
        Xa, Va, losses, accs = self.solver.update(
            self.loss_fn, self.spec, state.params[:k_active],
            state.w[:k_active], ckeys, data_active, lr,
        )
        X = state.params.at[:k_active].set(Xa)
        mom = (
            state.mom.at[:k_active].set(Va)
            if state.mom is not None else None
        )
        P = topology.NeighborList(slots.idx, slots.wgt)
        Xm, w_new, comp, _, extras = comm_phase(
            self.compressor, self.mixer, P, X, state.w, state.comp, (),
            t=state.round,
        )
        losses_res = state.losses.at[:k_active].set(losses)
        new_state = FLState(
            Xm, mom, w_new, state.key, state.round + 1, losses_res, comp, ()
        )
        # w_sum counts every resident slot; the runner subtracts the
        # (c_max - c) inert unit pads to report real closure mass.
        metrics = {
            "loss": losses.mean(), "acc": accs.mean(),
            "w_sum": w_new.sum(), **extras,
        }
        return new_state, metrics

    # -- whole training runs inside one jit ---------------------------------

    def run(self, state: FLState, rounds: int):
        """``lax.scan`` ``rounds`` steps; returns (state, stacked metrics)."""
        return jax.lax.scan(
            lambda s, _: self.step(s), state, None, length=rounds
        )

    # -- jit-resident supersteps (the production driver) ---------------------

    def make_eval_fn(self, test_data, batch: int = 1024):
        """Jittable masked fixed-shape evaluation of the consensus model.

        The test set is padded and stacked into ``(n_chunks, batch, ...)``
        constants once, so ``eval_fn(state) -> (test_loss, test_acc)`` has a
        single fixed shape regardless of the ragged final chunk and can run
        inside ``lax.scan``/``lax.cond``.  Per-example metrics are vmapped
        and the pad rows masked out of the sums exactly (``where``, not
        multiply — a non-finite loss on a zero pad row must not poison the
        sum via ``NaN * 0``).
        """
        n = test_data["x"].shape[0]
        n_chunks = -(-n // batch)
        total = n_chunks * batch
        padded = {
            k: jnp.concatenate(
                [v, jnp.zeros((total - n,) + v.shape[1:], v.dtype)]
            ).reshape((n_chunks, batch) + v.shape[1:])
            for k, v in test_data.items()
        }
        mask = (jnp.arange(total) < n).reshape(n_chunks, batch)

        @jax.named_scope("eval")
        def eval_fn(state: FLState):
            row = (
                state.params
                if self.mixer.kind == "central"
                else state.params.mean(axis=0)
            )
            params = self.spec.unravel(row)

            def one(ex):
                return self.loss_fn(
                    params, jax.tree.map(lambda v: v[None], ex)
                )

            def chunk_sums(carry, cm):
                chunk, m = cm
                per_l, per_a = jax.vmap(one)(chunk)
                return (
                    carry[0] + jnp.sum(jnp.where(m, per_l, 0.0)),
                    carry[1] + jnp.sum(jnp.where(m, per_a, 0.0)),
                ), None

            (tl, ta), _ = jax.lax.scan(
                chunk_sums,
                (jnp.float32(0.0), jnp.float32(0.0)),
                (padded, mask),
            )
            return tl / n, ta / n

        return eval_fn

    def run_superstep(
        self,
        state: FLState,
        rounds: int,
        eval_every: int = 0,
        test_data=None,
        eval_batch: int = 1024,
    ):
        """One jit-resident superstep: ``lax.scan`` ``rounds`` rounds inside
        a single jit with donated carry, evaluating *in-scan* on
        ``test_data`` whenever the global round counter hits ``eval_every``
        (the cadence follows ``state.round``, so it is stable across
        superstep boundaries and checkpoint resume).

        Returns ``(state, history)`` where every history leaf is stacked
        ``(rounds,)``; with eval enabled, ``history`` additionally carries
        ``test_loss`` / ``test_acc`` and the boolean ``eval_mask`` marking
        which rounds the eval values are valid for (non-eval rounds hold
        zeros).  Compiled drivers are memoized per (rounds, eval_every,
        test_data identity, eval_batch), so repeated supersteps of the same
        shape reuse one executable.  The client data is an argument of the
        compiled driver, not a constant inside it.
        """
        cache_key = (
            int(rounds), int(eval_every),
            id(test_data) if test_data is not None else None,
            int(eval_batch),
        )
        # The cache entry keeps a strong reference to test_data: an id() in
        # the key can only collide with a *live* dict, and a live id is the
        # same object — so a hit can never serve constants baked from a
        # different (freed, address-reused) test set.
        entry = self._superstep_cache.get(cache_key)
        fn = entry[0] if entry is not None else None
        if fn is None:
            eval_fn = (
                self.make_eval_fn(test_data, eval_batch)
                if test_data is not None and eval_every
                else None
            )

            def body(data, s, _):
                s, metrics = self.step(s, data)
                if eval_fn is not None:
                    # s.round is already the post-increment (1-based) count.
                    do = jnp.mod(s.round, eval_every) == 0
                    tl, ta = jax.lax.cond(
                        do,
                        eval_fn,
                        lambda _s: (jnp.float32(0.0), jnp.float32(0.0)),
                        s,
                    )
                    metrics = dict(
                        metrics, test_loss=tl, test_acc=ta, eval_mask=do
                    )
                return s, metrics

            fn = jax.jit(
                lambda s, data: jax.lax.scan(
                    functools.partial(body, data), s, None, length=rounds
                ),
                donate_argnums=0,
            )
            self._superstep_cache[cache_key] = (fn, test_data)
        return fn(state, self.data)


def make_program(
    loss_fn: Callable,
    init_fn: Callable,
    client_data,
    algo,
    topo: topology.TopologyConfig,
    participation: float = 0.1,
    gossip: str = "auto",
    link: topology.LinkModel | None = None,
    churn: topology.ChurnModel | None = None,
    mesh=None,
    shard_axis: str = "clients",
    delta: DeltaConfig | int | str | None = None,
    bank_dtype=None,
) -> RoundProgram:
    """Compose an ``AlgoConfig`` into a :class:`RoundProgram`.

    The bank spec is built from ``jax.eval_shape`` of ``init_fn`` — no
    parameters are materialized here; ``program.init`` owns that.  With
    ``delta`` (a :class:`~repro.core.flat.DeltaConfig`, or just a rank /
    ``"full"``) the bank stores per-client low-rank adapter rows over a
    frozen shared base materialized once from ``init_fn`` — every solver /
    compressor / mixer then operates verbatim on the narrower
    ``(n, d_delta)`` bank.  ``bank_dtype`` overrides the bank storage dtype
    (e.g. ``jnp.bfloat16`` rows with float32 momentum — the EF residual
    stays float32, so top-k error feedback remains exact).

    ``gossip`` picks the mixing-operator representation AND (with a mesh)
    the executor, through the one dispatch rule in
    :func:`repro.comm.plan.resolve_backend`: ``"auto"`` (default) applies
    the density rule in :func:`repro.kernels.ops.use_sparse_gossip` to the
    family's static ``k_max``; ``"sparse"`` / ``"dense"`` force
    neighbor-list or dense sampling (benchmarks compare the two; small
    recorded configs always resolve dense, keeping the golden traces
    bit-for-bit); ``"xla"`` forces the sparse form on the partitionable
    all-gather executor; ``"halo"`` (mesh required) forces the sparse form
    on the ``shard_map`` halo exchange that ships only each shard's
    :class:`~repro.comm.plan.CommPlan` rows.  Under a mesh, ``"auto"`` /
    ``"sparse"`` select halo automatically for the static shift families
    (ring / exponential[_cycle]) and the all-gather otherwise.

    ``link`` is the unreliable-link scenario (:class:`topology.LinkModel`):
    per-round i.i.d. edge drops (renormalized before the send, so ``P_t``
    stays exactly column-stochastic), bounded per-edge delivery delays
    (``DelayedPushSumMixer`` with its in-flight buffers in the round
    state), or event-triggered transmission (``EventTriggeredMixer`` with
    the ``comm_fraction`` metric).  ``None`` — or a model whose fields are
    all zero — builds the exact perfect-link program, bitwise.

    ``churn`` is the node-failure scenario (:class:`topology.ChurnModel`):
    whole clients crash and (optionally) rejoin per round, their in/out
    edges masked from the sampled operator before sender normalization and
    their push-sum mass frozen on the self-loop, keeping
    live + in-flight + frozen mass == n exactly.  Composes with ``link``
    drops and delays (churn masks first, drops fail surviving edges);
    rejected with ``event_threshold``.  ``None`` — or an all-zero model —
    builds the exact immortal-population program, bitwise.

    ``mesh`` row-shards the whole round: bank rows (and the client data)
    are partitioned along ``shard_axis``, the mixers are re-backed onto a
    partitionable gossip executor — the all-gather form or the halo
    exchange, per the dispatch rule above — and ``init``/``step``/
    ``run_superstep`` then run sharded under one jit: intra-shard edges
    stay local, cross-shard edges become one row collective (the full bank
    on the all-gather path, only the plan's O(k) halo rows on the halo
    path).  ``None`` is the exact single-device program.
    """
    from repro.kernels import ops as kops

    solver, compressor, mixer = make_stages(algo)
    if topo.kind == "two_tier":
        if mixer.kind != "directed":
            raise ValueError(
                "the two-tier family is directed push-sum gossip only; "
                f"comm={algo.comm!r} has no two-tier form"
            )
        if algo.selection:
            raise ValueError(
                "loss-selective neighbor sampling has no two-tier form; "
                "disable selection for kind='two_tier'"
            )
    link = link if link is not None and link.active else None
    if link is not None:
        if mixer.kind == "central":
            raise ValueError(
                "the central (server) round has no peer links to degrade; "
                "drop the link model for comm='central'"
            )
        if mixer.kind != "directed" and (link.delay or link.event_threshold):
            raise ValueError(
                "delayed / event-triggered mixing is push-sum (directed) "
                f"only, not comm={algo.comm!r}; symmetric gossip supports "
                "link drops alone"
            )
        if link.delay:
            mixer = DelayedPushSumMixer(delay=link.delay)
        elif link.event_threshold:
            mixer = EventTriggeredMixer(
                threshold=link.event_threshold,
                decay=link.event_decay,
                schedule=link.event_schedule,
            )
    churn = churn if churn is not None and churn.active else None
    if churn is not None:
        if mixer.kind == "central":
            raise ValueError(
                "the central (server) round has no peer population to "
                "churn; drop churn= for comm='central'"
            )
        if link is not None and link.event_threshold:
            # The event mixer keeps ONE last-broadcast row per sender; a
            # node that crashed after its last transmission would keep
            # being mixed from the cache by peers that can no longer hear
            # it (sound modeling needs per-receiver caches).
            raise ValueError(
                "event-triggered mixing assumes immortal senders (the "
                "shared last-broadcast cache cannot model a crashed "
                "transmitter); churn and event_threshold do not compose"
            )
    if mixer.kind == "central" and not isinstance(
        compressor, IdentityCompressor
    ):
        # The central round has no gossip step to compress; silently
        # training uncompressed would misreport communication savings.
        raise ValueError(
            "central (server) rounds do not model compressed communication; "
            f"drop compressor={algo.compressor!r}/quantize_gossip"
        )
    if gossip not in ("auto", "sparse", "dense", "xla", "halo"):
        raise ValueError(
            f"gossip must be auto|sparse|dense|xla|halo, got {gossip!r}"
        )
    if mixer.kind == "central":
        sparse_mix = False
    elif gossip in ("sparse", "xla", "halo"):
        if topo.kind == "full":
            raise ValueError(
                "the full graph has no sparse neighbor-list form"
            )
        sparse_mix = True
    elif gossip == "dense":
        sparse_mix = False
    else:
        sparse_mix = kops.use_sparse_gossip(
            topo.n_clients, topology.neighbor_k_max(topo, mixer.kind)
        )
    if (link is not None and link.drop > 0 and sparse_mix
            and mixer.kind == "symmetric"):
        raise ValueError(
            "link drops on the symmetric neighbor-list form are "
            "unsupported; pass gossip='dense' for symmetric + drops"
        )
    if (link is not None and link.drop > 0 and sparse_mix
            and topo.kind == "two_tier"):
        raise ValueError(
            "link drops on the two-tier operator form are unsupported; "
            "pass gossip='dense' for two_tier + drops"
        )
    if churn is not None and sparse_mix and mixer.kind == "symmetric":
        raise ValueError(
            "churn on the symmetric neighbor-list form is unsupported; "
            "pass gossip='dense' for symmetric + churn"
        )
    if churn is not None and sparse_mix and topo.kind == "two_tier":
        raise ValueError(
            "churn on the two-tier operator form is unsupported; "
            "pass gossip='dense' for two_tier + churn"
        )
    if mesh is not None:
        if shard_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has no {shard_axis!r} axis (axes: {mesh.axis_names})"
            )
        n_dev = mesh.shape[shard_axis]
        if topo.n_clients % n_dev:
            raise ValueError(
                f"n_clients={topo.n_clients} must be divisible by the "
                f"{shard_axis!r} axis size {n_dev} to row-shard the bank"
            )
        if mixer.kind == "central":
            raise ValueError(
                "the central (server) round keeps one global row — there "
                "is no client bank to shard; drop the mesh"
            )
        # Client-stacked data rows live with their bank rows, so the
        # vmapped local phase never moves examples across shards.
        from jax.sharding import NamedSharding, PartitionSpec

        def _row_put(x):
            spec = [shard_axis] + [None] * (x.ndim - 1)
            return jax.device_put(
                x, NamedSharding(mesh, PartitionSpec(*spec))
            )

        client_data = jax.tree.map(_row_put, client_data)
        # The solver's fused update kernel runs per shard (shard_map).
        solver = dataclasses.replace(
            solver, mesh=mesh, shard_axis=shard_axis)
    if mixer.kind != "central":
        from repro.comm.plan import resolve_backend

        backend = resolve_backend(
            gossip, sparse_mix, topo, mixer.kind, mesh, shard_axis
        )
        if backend is not None:
            # The interpret-mode kernel executors (pallas grids, fori_loop
            # panel slicing) defeat the GSPMD partitioner; under a mesh the
            # mixer is re-backed onto a partitionable executor: the
            # all-gather twin ("xla" — same accumulation order, bitwise)
            # or the shard_map halo exchange (a HaloBackend shipping only
            # the CommPlan's remote rows per shard).
            mixer = dataclasses.replace(mixer, backend=backend)
    shape_tree = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    if delta is not None:
        if not isinstance(delta, DeltaConfig):
            delta = DeltaConfig(rank=delta)
        if mixer.kind == "central":
            raise ValueError(
                "the central (server) round keeps one global row — there "
                "are no per-client deltas to bank; drop delta= for "
                "comm='central'"
            )
        dspec = make_delta_spec(
            shape_tree, rank=delta.rank, adapt=delta.adapt, dtype=bank_dtype
        )
        if dspec.dim == 0:
            raise ValueError(
                f"delta adapt={delta.adapt!r} selected no leaves: every "
                "client would be frozen at the base model"
            )
        # The frozen shared base is materialized exactly once, here; rows
        # in the bank are pure adapter payloads over it.
        base = init_fn(jax.random.PRNGKey(delta.base_seed))
        spec = bind_delta_spec(dspec, base)
    else:
        spec = make_spec(shape_tree, dtype=bank_dtype)
    # Exponential graphs cycle through log2(n) hop matrices; precompute
    # the stack once so the (traced) round index can select the graph.
    exp_cycle = None
    if topo.kind == "exponential" and topo.time_varying:
        exp_cycle = (
            topology.neighbors_exponential_cycle(topo.n_clients)
            if sparse_mix
            else topology.exponential_cycle(topo.n_clients)
        )
    return RoundProgram(
        solver=solver,
        compressor=compressor,
        mixer=mixer,
        loss_fn=loss_fn,
        init_fn=init_fn,
        data=client_data,
        topo=topo,
        spec=spec,
        n=topo.n_clients,
        participation=participation,
        lr=algo.lr,
        lr_decay=algo.lr_decay,
        selection=algo.selection,
        exp_cycle=exp_cycle,
        gossip=gossip,
        sparse_mix=sparse_mix,
        link=link,
        linked=link is not None or mixer.link_stateful,
        churn_model=churn,
        mesh=mesh,
        shard_axis=shard_axis,
    )
