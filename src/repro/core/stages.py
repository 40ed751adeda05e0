"""Composable round stages: LocalSolver / Compressor / Mixer.

Algorithm 1 of the paper is three stages, and so is every DFL variant in
the related work — same round, different stage:

  LocalSolver   lines 4-11: K local iterations over the flat (n, D) bank
                (SAM two-pass gradients + momentum; plain SGD and a
                FedProx-style proximal solver are drop-in swaps).
  Compressor    what leaves the client before communication: identity,
                per-row int8 quantize/dequantize, or top-k sparsification
                with error feedback (persistent residual state).
  Mixer         lines 12-14: push-sum over a directed column-stochastic
                matrix, doubly-stochastic symmetric gossip (DFedSAM), or a
                central server reduce (FedAvg).

Every stage is a frozen config dataclass with a pure ``init_state`` /
``apply``-style method pair operating on the flat ``(n_clients, D)`` bank,
so the Pallas ``gossip_matmul`` / ``fused_update`` kernels stay the hot
path and any composition is jittable and ``lax.scan``-able end to end.
``repro.core.program`` wires three stages into a round program; the
``SOLVERS`` / ``COMPRESSORS`` / ``MIXERS`` registries map ``AlgoConfig``
fields to stage instances.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import pushsum
from repro.core.sam import sam_gradient
from repro.kernels import ops as kops

__all__ = [
    "SamMomentumSolver",
    "ProximalSolver",
    "IdentityCompressor",
    "Int8RowCompressor",
    "TopKEFCompressor",
    "LinkState",
    "ChurnState",
    "PushSumMixer",
    "SymmetricMixer",
    "DelayedPushSumMixer",
    "EventTriggeredMixer",
    "CentralMixer",
    "SOLVERS",
    "COMPRESSORS",
    "MIXERS",
    "make_stages",
    "comm_phase",
]


def _sample_batch(data: dict, key: jax.Array, batch_size: int):
    m = data["x"].shape[0]
    idx = jax.random.randint(key, (batch_size,), 0, m)
    return {k: v[idx] for k, v in data.items()}


# ---------------------------------------------------------------------------
# LocalSolver: (X, w, keys, data, lr) -> (X, V, losses, accs) on the bank.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SamMomentumSolver:
    """Algorithm 1 lines 4-11 for all clients at once: gradients are vmapped
    over bank rows, the momentum/descent/de-bias step is one fused kernel
    call on the whole bank.  ``rho=0`` degrades to a single gradient pass,
    ``alpha=0`` to plain SGD (the momentum bank drops out of the carry).

    ``mesh`` (set by ``make_program`` for a row-sharded bank) runs the
    fused kernel once per shard under ``shard_map``: the compiler cannot
    partition a Mosaic kernel itself, and the update is row-local."""

    local_steps: int = 5
    batch_size: int = 32
    rho: float = 0.0
    alpha: float = 0.0
    mesh: Any = None
    shard_axis: str = "clients"

    def _fused_update(self, X, V, G, alpha, lr, w):
        if self.mesh is None:
            return kops.fused_update_bank(X, V, G, alpha, lr, w)
        from jax.sharding import PartitionSpec

        row, rep = PartitionSpec(self.shard_axis), PartitionSpec()
        return jax.shard_map(
            lambda x, v, g, lr_, w_: kops.fused_update_bank(
                x, v, g, alpha, lr_, w_),
            mesh=self.mesh,
            in_specs=(row, row, row, rep, row),
            out_specs=(row, row, row),
            check_vma=False,
        )(X, V, G, lr, w)

    def _grad_one(self, loss_fn, spec):
        def grad_one(x_i, w_i, key_i, data_i):
            key_i, bk = jax.random.split(key_i)
            batch = _sample_batch(data_i, bk, self.batch_size)
            # Unravel OUTSIDE the differentiated closure, fusing the line-5
            # de-bias into the leaf slices; the gradient stays leaf-shaped
            # (no scatter back into a (D,) row per leaf) and is ravelled
            # once — one contiguous write per client.  ``spec.debias`` is
            # ``unravel(x) / w`` for the dense bank and ``base +
            # expand(x) / w`` for the delta bank.
            z_tree = spec.debias(x_i, w_i)
            g_tree, (loss, acc) = sam_gradient(
                loss_fn, z_tree, batch, self.rho
            )  # lines 6-8
            return key_i, g_tree, loss, acc

        return grad_one

    @staticmethod
    def _grads(grad_one, spec, X, w, ks, data):
        """One local step's gradients for every client, raveled into the
        (n, D) bank: the ``sam_grad`` and ``grad_ravel`` phases of the
        round, named for the profiler."""
        with jax.named_scope("sam_grad"):
            ks, G_tree, losses, accs = jax.vmap(grad_one)(X, w, ks, data)
        with jax.named_scope("grad_ravel"):
            G = spec.ravel_grad_stacked(G_tree, X)  # one contiguous write
        return ks, G, losses, accs

    def update(self, loss_fn, spec, X, w, keys, data, lr):
        grad_one = self._grad_one(loss_fn, spec)
        V0 = jnp.zeros_like(X, jnp.float32)

        if self.alpha == 0.0:
            # Momentum off: v' = g exactly, so the momentum bank is never
            # read — keep it out of the scan carry and let XLA fold
            # ``0 * 0 + g`` and DCE the v write on the CPU inline path.
            # V0 doubles as the kernel's zero momentum operand (one (n, D)
            # zero bank, not two identical ones).

            def step0(carry, _):
                X, ks = carry
                ks, G, losses, accs = self._grads(grad_one, spec, X, w, ks,
                                                  data)
                X, _, _ = self._fused_update(X, V0, G, 0.0, lr, w)
                return (X, ks), (losses, accs)

            (X, _), (losses, accs) = jax.lax.scan(
                step0, (X, keys), None, length=self.local_steps
            )
            return X, V0, losses.mean(axis=0), accs.mean(axis=0)

        def step(carry, _):
            X, V, ks = carry
            ks, G, losses, accs = self._grads(grad_one, spec, X, w, ks, data)
            # Lines 9-11 fused over the whole bank.  The next step de-biases
            # its own rows (``spec.debias``), so the de-biased z output, XLA
            # outside the kernel, is dead-code eliminated.
            X, V, _ = self._fused_update(X, V, G, self.alpha, lr, w)
            return (X, V, ks), (losses, accs)

        (X, V, _), (losses, accs) = jax.lax.scan(
            step, (X, V0, keys), None, length=self.local_steps
        )
        return X, V, losses.mean(axis=0), accs.mean(axis=0)


@dataclasses.dataclass(frozen=True)
class ProximalSolver(SamMomentumSolver):
    """FedProx-style local objective f_i(x) + (mu/2) ||x - x_round||^2
    (Li et al. 2020; DFedADMM's dual-constrained solver is the same shape).
    The proximal pull is applied directly on the bank — ``G += mu (X - X0)``
    with X0 the round-start bank — so it composes with any mixer."""

    mu: float = 0.01

    def update(self, loss_fn, spec, X, w, keys, data, lr):
        grad_one = self._grad_one(loss_fn, spec)
        X0 = X  # round-start reference, constant through the local scan
        V0 = jnp.zeros_like(X, jnp.float32)

        if self.alpha == 0.0:
            # Same alpha==0 treatment as SamMomentumSolver: v' = g exactly,
            # so the momentum bank leaves the scan carry and V0 doubles as
            # the kernel's zero momentum operand — one (n, D) zero bank.
            def step0(carry, _):
                X, ks = carry
                ks, G, losses, accs = self._grads(grad_one, spec, X, w, ks,
                                                  data)
                G = G + self.mu * (X - X0).astype(G.dtype)
                X, _, _ = self._fused_update(X, V0, G, 0.0, lr, w)
                return (X, ks), (losses, accs)

            (X, _), (losses, accs) = jax.lax.scan(
                step0, (X, keys), None, length=self.local_steps
            )
            return X, V0, losses.mean(axis=0), accs.mean(axis=0)
        return self._update_momentum(grad_one, spec, X, X0, V0, w, keys,
                                     data, lr)

    def _update_momentum(self, grad_one, spec, X, X0, V0, w, keys, data, lr):
        """Generic momentum-carrying path (also valid, if wasteful, at
        alpha == 0 — the fast path above is pinned bitwise against it)."""

        def step(carry, _):
            X, V, ks = carry
            ks, G, losses, accs = self._grads(grad_one, spec, X, w, ks, data)
            G = G + self.mu * (X - X0).astype(G.dtype)
            X, V, _ = self._fused_update(X, V, G, self.alpha, lr, w)
            return (X, V, ks), (losses, accs)

        (X, V, _), (losses, accs) = jax.lax.scan(
            step, (X, V0, keys), None, length=self.local_steps
        )
        return X, V, losses.mean(axis=0), accs.mean(axis=0)


# ---------------------------------------------------------------------------
# Compressor: init_state(n, d) -> state; apply(state, X) -> (state, X').
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IdentityCompressor:
    """No-op communication stage (full-precision gossip)."""

    stateful = False

    def init_state(self, n: int, d: int):
        return ()

    def apply(self, state, X):
        return state, X


@dataclasses.dataclass(frozen=True)
class Int8RowCompressor:
    """Int8 symmetric quantization with one scale per client row of the
    flat bank — tighter than a per-leaf global scale."""

    stateful = False

    def init_state(self, n: int, d: int):
        return ()

    def apply(self, state, X):
        Xf = X.astype(jnp.float32)
        scale = jnp.max(jnp.abs(Xf), axis=1, keepdims=True) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(Xf / scale), -127, 127)
        return state, (q * scale).astype(X.dtype)


@dataclasses.dataclass(frozen=True)
class TopKEFCompressor:
    """Per-row top-k sparsification with error feedback (Stich et al. 2018).

    Each round the residual of what was dropped is carried in a float32
    ``(n, D)`` state bank and added back before the next top-k, so the
    compressed stream is unbiased in the long run:
    ``compressed + residual' == X + residual`` holds exactly.
    ``ratio`` is the kept fraction of coordinates per row (k = ratio * D).
    """

    ratio: float = 0.05
    stateful = True

    def init_state(self, n: int, d: int):
        return jnp.zeros((n, d), jnp.float32)

    def apply(self, state, X):
        y = X.astype(jnp.float32) + state
        k = max(int(self.ratio * y.shape[1]), 1)
        mag = jnp.abs(y)
        kth = jax.lax.top_k(mag, k)[0][:, -1:]
        mask = mag >= kth  # ties may keep a few extra coords — still sparse
        # The transmitted payload is the bank-dtype cast; the residual must
        # be taken against *that*, not the float32 top-k values, or the
        # sub-f32 rounding error is silently dropped instead of fed back
        # (compressed + residual' == X + residual then fails for bf16/f16).
        Xc = (y * mask).astype(X.dtype)
        return y - Xc.astype(jnp.float32), Xc


# ---------------------------------------------------------------------------
# Mixer: init_weights(n) -> w; mix(P, X, w) -> (X', w').
#
# ``mix_round`` is the full communication phase the round program drives:
#   mix_round(P, X, w, link, key, X_full) -> (X', w', link', extras)
# where X is the (possibly compressed) transmitted bank and X_full the
# uncompressed bank.  Every mixer keeps client i's OWN contribution at full
# precision — X'[i] = P[ii]·X_full[i] + sum_{j != i} P[ij]·X[j] — because no
# client quantizes/sparsifies the copy it hands to itself (the self-loop is
# local memory, not a network link).  ``link`` is the LinkState carry for
# stateful mixers (delayed payload buffers, event-trigger caches); stateless
# mixers thread it through untouched.
# ---------------------------------------------------------------------------


class LinkState(NamedTuple):
    """Unreliable-link carry threaded through the round state.

    ``key`` drives the per-round link randomness (drop masks, delay draws)
    on its own PRNG stream, so link-free programs keep a bit-identical main
    stream.  ``bufx``/``bufw`` are the bounded-staleness in-flight payload
    buffers of :class:`DelayedPushSumMixer` — ``bufx[r]`` is the ``(n, D)``
    mass arriving ``r + 1`` rounds from now, so total push-sum mass
    ``w.sum() + bufw.sum() == n`` exactly.  ``last`` is the ``(n, D)``
    last-broadcast cache of :class:`EventTriggeredMixer`.  Unused fields
    stay ``()`` and drop out of the pytree.
    """

    key: jax.Array
    bufx: Any = ()  # (B, n, D) in-flight payload mass (delayed mixer)
    bufw: Any = ()  # (B, n) in-flight push-sum mass (delayed mixer)
    last: Any = ()  # (n, D) last transmitted rows (event-triggered mixer)


class ChurnState(NamedTuple):
    """Node-churn carry threaded through the round state.

    ``key`` drives the per-round failure/recovery draws on its own PRNG
    stream (folded off the seed, so churn-free programs keep a
    bit-identical main stream).  ``live`` is the ``(n,)`` int8 liveness
    vector (``topology.LIVE`` / ``DOWN`` / ``DOWN_PERMANENT``).  ``tpl``
    carries the ``(D,)`` init template row only under cold resurrection
    (``ChurnModel(resurrect="cold")``) — a reborn node's de-biased model
    is reset to it; warm churn keeps ``tpl == ()`` and it drops out of
    the pytree.
    """

    key: jax.Array
    live: jnp.ndarray
    tpl: Any = ()


def _self_weights(P):
    """The self-loop weight per receiver: ``diag(P)`` for a dense matrix,
    slot 0 for a NeighborList (the self-loop by convention; pads and
    permutation self-hits carry weight 0 elsewhere).  For a TwoTierOp the
    self-loop lives on the intra-pod block diagonals — its inter list's
    slot 0 is a zero-weight pad."""
    from repro.core.topology import NeighborList, TwoTierOp

    if isinstance(P, TwoTierOp):
        return jnp.diagonal(P.intra, axis1=1, axis2=2).reshape(-1)
    if isinstance(P, NeighborList):
        return P.wgt[:, 0]
    return jnp.diagonal(P)


def _selfloop_correction(P, X, X_full, mixed):
    """Replace the self-loop contribution ``P[ii]·X[i]`` inside ``mixed``
    with the full-precision ``P[ii]·X_full[i]``.  When ``X_full is X``
    (identity compressor) this is a trace-time no-op, keeping those
    compositions bitwise unchanged."""
    if X_full is X:
        return mixed
    s = _self_weights(P)[:, None]
    return mixed + (s * (X_full.astype(jnp.float32) - X.astype(jnp.float32))
                    ).astype(mixed.dtype)


@dataclasses.dataclass(frozen=True)
class PushSumMixer:
    """Directed column-stochastic gossip + push-sum weight mixing
    (Algorithm 1 lines 12-14): X' = P X, w' = P w.

    ``backend`` is forwarded as ``use_kernel`` into the bank gossip —
    ``None`` keeps the size-based kernel auto-selection; sharded programs
    set ``"xla"`` so the GSPMD partitioner sees plain HLO."""

    backend: Any = None
    kind = "directed"
    link_stateful = False

    def init_weights(self, n: int):
        return jnp.ones((n,), jnp.float32)

    def link_buffers(self, bank) -> dict:
        return {}

    def mix_weights(self, P, w):
        return pushsum.gossip_weights(P, w)

    def mix(self, P, X, w):
        return pushsum.gossip_bank(P, X, self.backend), self.mix_weights(P, w)

    def mix_round(self, P, X, w, link, key, X_full, t=None):
        Xm, wm = self.mix(P, X, w)
        return _selfloop_correction(P, X, X_full, Xm), wm, link, {}


@dataclasses.dataclass(frozen=True)
class SymmetricMixer:
    """Doubly-stochastic gossip over an undirected graph (DFedAvg /
    DFedSAM family): X' = W X, push-sum weights stay all-ones."""

    backend: Any = None
    kind = "symmetric"
    link_stateful = False

    def init_weights(self, n: int):
        return jnp.ones((n,), jnp.float32)

    def link_buffers(self, bank) -> dict:
        return {}

    def mix_weights(self, P, w):
        return w

    def mix(self, P, X, w):
        return pushsum.gossip_bank(P, X, self.backend), self.mix_weights(P, w)

    def mix_round(self, P, X, w, link, key, X_full, t=None):
        Xm, wm = self.mix(P, X, w)
        return _selfloop_correction(P, X, X_full, Xm), wm, link, {}


def _delay_slices(key, P, bound: int):
    """Per-edge delivery delays in {0..bound} as a list of ``bound + 1``
    disjoint mixing operators: slice d carries exactly the edges arriving
    d rounds late; self-loops always land in slice 0.  Summing the slices
    recovers ``P`` exactly, so each column's total outgoing mass is still
    1 — it is merely spread over delivery times."""
    from repro.core.topology import NeighborList

    if isinstance(P, NeighborList):
        d = jax.random.randint(key, P.idx.shape, 0, bound + 1)
        d = d.at[:, 0].set(0)  # the self-loop is local: never delayed
        return [
            NeighborList(P.idx, jnp.where(d == t, P.wgt, 0.0))
            for t in range(bound + 1)
        ]
    n = P.shape[0]
    d = jax.random.randint(key, (n, n), 0, bound + 1)
    d = jnp.where(jnp.eye(n, dtype=bool), 0, d)
    return [P * (d == t) for t in range(bound + 1)]


@dataclasses.dataclass(frozen=True)
class DelayedPushSumMixer:
    """Push-sum over links with bounded random delays (staleness <= B).

    Every surviving edge (j -> i) samples a delivery delay d in {0..B}
    each round; the share ``P[ij]·(x_j, w_j)`` it carries is *in flight*
    for d rounds, riding the ``(B, n, D)`` / ``(B, n)`` buffers in
    :class:`LinkState`, and is added to receiver i when it matures.  The
    self-loop is local memory and always delivers instantly.  Because a
    sender's full column mass leaves every round (just spread over
    delivery times), total push-sum mass is exact at every round:
    ``w.sum() + bufw.sum() == n`` — no silent mass leak, and the de-biased
    ratio z = x / w still converges to the true average (Assran et al.
    2019 treat exactly this overlap/staleness regime for SGP).
    """

    delay: int = 1
    backend: Any = None
    kind = "directed"
    link_stateful = True

    def __post_init__(self):
        if self.delay < 1:
            raise ValueError("DelayedPushSumMixer needs delay >= 1; "
                             "use PushSumMixer for instantaneous links")

    def init_weights(self, n: int):
        return jnp.ones((n,), jnp.float32)

    def link_buffers(self, bank) -> dict:
        n = bank.shape[0]
        return {
            "bufx": jnp.zeros((self.delay,) + bank.shape, bank.dtype),
            "bufw": jnp.zeros((self.delay, n), jnp.float32),
        }

    def mix_weights(self, P, w):
        return pushsum.gossip_weights(P, w)

    def mix_round(self, P, X, w, link: LinkState, key, X_full, t=None):
        slices = _delay_slices(key, P, self.delay)
        sent_x = [pushsum.gossip_bank(Ps, X, self.backend) for Ps in slices]
        sent_w = [pushsum.gossip_weights(Ps, w) for Ps in slices]
        # Slice 0 holds the self-loop: keep it full precision.
        sent_x[0] = _selfloop_correction(P, X, X_full, sent_x[0])
        X_new = sent_x[0] + link.bufx[0].astype(sent_x[0].dtype)
        w_new = sent_w[0] + link.bufw[0]
        # Shift the buffers one round closer to delivery and enqueue the
        # newly sent delayed shares.
        bufx = jnp.concatenate(
            [link.bufx[1:], jnp.zeros_like(link.bufx[:1])], axis=0
        ) + jnp.stack(sent_x[1:]).astype(link.bufx.dtype)
        bufw = jnp.concatenate(
            [link.bufw[1:], jnp.zeros_like(link.bufw[:1])], axis=0
        ) + jnp.stack(sent_w[1:])
        link = link._replace(bufx=bufx, bufw=bufw)
        return X_new, w_new, link, {"w_inflight": bufw.sum()}


@dataclasses.dataclass(frozen=True)
class EventTriggeredMixer:
    """Directed push-sum where a client transmits a fresh row only when it
    drifted more than ``threshold`` (L2) from its last transmission;
    neighbors otherwise mix the receiver-side cached last broadcast
    (`LinkState.last`).  The self-loop always uses the live full-precision
    row — a client never reads itself through the network.  Push-sum
    weights are scalars (n floats per round, vs n·D for the bank) and are
    always mixed fresh, so mass stays exactly n; the consensus error this
    scheme admits is bounded by the threshold, which is the knob the
    ``comm_fraction`` extra (fraction of clients that transmitted) trades
    against.

    The threshold may be a *schedule* (adaptive communication censoring):
    ``schedule(t)`` when given, else ``threshold * decay ** t`` — a
    decaying threshold communicates sparsely early and tightens toward
    full gossip as training converges.  ``decay == 1.0`` with no schedule
    is resolved at trace time to the fixed-threshold mixer, bitwise.
    """

    threshold: float = 0.01
    # Per-round multiplicative threshold decay; 1.0 = fixed threshold.
    decay: float = 1.0
    # Optional callable ``t -> threshold`` (t is the traced round index);
    # overrides ``decay``.  Must be jit-traceable.
    schedule: Any = None
    backend: Any = None
    kind = "directed"
    link_stateful = True

    def _threshold_at(self, t):
        if self.schedule is None and self.decay == 1.0:
            return self.threshold
        if t is None:
            raise ValueError(
                "a scheduled/decaying event threshold needs the round "
                "index: thread t=state.round into comm_phase (the pod "
                "round path supports fixed thresholds only)"
            )
        tf = jnp.asarray(t, jnp.float32)
        if self.schedule is not None:
            return jnp.asarray(self.schedule(tf), jnp.float32)
        return jnp.float32(self.threshold) * jnp.float32(self.decay) ** tf

    def init_weights(self, n: int):
        return jnp.ones((n,), jnp.float32)

    def link_buffers(self, bank) -> dict:
        # Every client's initial row is common knowledge (broadcast init),
        # so the cache starts warm: round 1 only transmits real movement.
        # A copy, not the bank itself — the carry is donated and two
        # aliases of one buffer cannot both be.
        return {"last": jnp.array(bank)}

    def mix_weights(self, P, w):
        return pushsum.gossip_weights(P, w)

    def mix_round(self, P, X, w, link: LinkState, key, X_full, t=None):
        drift = X.astype(jnp.float32) - link.last.astype(jnp.float32)
        send = jnp.sqrt(jnp.sum(drift * drift, axis=1)) > self._threshold_at(t)
        B = jnp.where(send[:, None], X, link.last.astype(X.dtype))
        Xm = pushsum.gossip_bank(P, B, self.backend)
        # The self-loop never reads the cache: always the live full bank
        # (B is a fresh array, so the helper's is-X short-circuit never
        # swallows the correction).
        Xm = _selfloop_correction(P, B, X_full, Xm)
        wm = pushsum.gossip_weights(P, w)
        link = link._replace(last=B)
        return Xm, wm, link, {
            "comm_fraction": send.astype(jnp.float32).mean()
        }


@dataclasses.dataclass(frozen=True)
class CentralMixer:
    """Central-server round (FedAvg): the sampled clients' rows are averaged
    into the single global row; no mixing matrix, no push-sum weights."""

    kind = "central"
    link_stateful = False

    def init_weights(self, n: int):
        return jnp.ones((n,), jnp.float32)

    def link_buffers(self, bank) -> dict:
        return {}

    def reduce(self, X):
        return X.mean(axis=0)


# ---------------------------------------------------------------------------
# The shared communication phase (compress -> link -> mix) — one definition
# driving both the flat-bank round program and the pod round_step.
# ---------------------------------------------------------------------------


def _identity(x):
    return x


def comm_phase(compressor, mixer, P, X, w, comp, link, *,
               linked=False, link_model=None, symmetric=False,
               pin=_identity, pin_link=_identity, t=None):
    """One communication phase on a flat ``(n, D)`` bank:

      compress -> split the link PRNG stream -> apply link drops ->
      ``mixer.mix_round`` -> re-pin the sharded outputs.

    ``pin``/``pin_link`` are GSPMD row-sharding constraints (identity when
    unsharded — every op then reduces to exactly the sequence the program
    and the pod ``round_step`` used to inline, bitwise).  Under a mesh they
    re-assert the bank's ``clients``-axis layout at the phase boundaries so
    the partitioner cannot rematerialize the bank replicated around the
    compressor/mixer reshapes.

    ``t`` is the (traced) round index, consumed only by mixers with a
    per-round schedule (the event-trigger threshold decay); ``None`` keeps
    every fixed-schedule composition bitwise unchanged.

    Returns ``(X_mixed, w_new, comp, link, extras)``.
    """
    X = pin(X)
    if compressor.stateful:
        comp = pin(comp)
    comp, Xc = compressor.apply(comp, X)
    lkey = None
    if linked:
        lkey, nkey = jax.random.split(link.key)
        link = link._replace(key=nkey)
        if link_model is not None and link_model.drop > 0:
            dkey, lkey = jax.random.split(lkey)
            P = link_model.drop_links(dkey, P, symmetric=symmetric)
        link = pin_link(link)
    Xm, w_new, link, extras = mixer.mix_round(P, Xc, w, link, lkey, X, t=t)
    Xm = pin(Xm)
    if compressor.stateful:
        comp = pin(comp)
    if linked:
        link = pin_link(link)
    return Xm, w_new, comp, link, extras


# ---------------------------------------------------------------------------
# Registries: AlgoConfig -> stage instances.
# ---------------------------------------------------------------------------

SOLVERS = {
    # Algorithm 1 inner loop; rho/alpha = 0 recover SGD+momentum / SAM-only.
    "sam_momentum": lambda a: SamMomentumSolver(
        a.local_steps, a.batch_size, a.rho, a.alpha),
    # Plain SGD regardless of the config's rho/alpha knobs.
    "sgd": lambda a: SamMomentumSolver(a.local_steps, a.batch_size, 0.0, 0.0),
    # FedProx-style proximal local objective (uses a.prox_mu).
    "proximal": lambda a: ProximalSolver(
        a.local_steps, a.batch_size, a.rho, a.alpha, mu=a.prox_mu),
}

COMPRESSORS = {
    "identity": lambda a: IdentityCompressor(),
    "int8_rows": lambda a: Int8RowCompressor(),
    # getattr: configs without a topk_ratio field (e.g. the pod StepConfig)
    # still resolve, so the stateful-compressor rejection can fire with its
    # own message instead of an AttributeError.
    "topk_ef": lambda a: TopKEFCompressor(getattr(a, "topk_ratio", 0.05)),
}

MIXERS = {
    "directed": lambda a: PushSumMixer(),
    "symmetric": lambda a: SymmetricMixer(),
    "central": lambda a: CentralMixer(),
}


def make_stages(algo):
    """Resolve an ``AlgoConfig`` into its (solver, compressor, mixer)
    composition.  ``algo.comm`` selects the mixer; ``quantize_gossip`` is the
    legacy spelling of ``compressor="int8_rows"``."""
    comp_name = algo.compressor
    if comp_name == "identity" and algo.quantize_gossip:
        comp_name = "int8_rows"
    try:
        solver = SOLVERS[algo.solver](algo)
        compressor = COMPRESSORS[comp_name](algo)
        mixer = MIXERS[algo.comm](algo)
    except KeyError as e:
        raise ValueError(f"unknown stage {e.args[0]!r} in {algo}") from None
    return solver, compressor, mixer
