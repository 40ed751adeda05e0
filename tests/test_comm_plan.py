"""CommPlan: the single communication-plan layer under mix, dispatch, store.

Pins the contracts the three consumers rely on: (1) the per-family k_in
table is ONE table (family_k_in == neighbor_k_max - 1 == active_k_in);
(2) the plan's in-neighbor sets equal the nonzero off-diagonal columns of
the densified sampled operator for every family, including every hop of
the time-varying exponential cycle; (3) the static ShiftLeg transport
delivers exactly the remote rows each shard's receivers read, and the
dynamic capacity is never exceeded by a sampled realization; (4) the
backend dispatch rule routes dense / sparse / xla / halo as documented;
(5) `launch.sharding.constrain` skips sharding constraints inside a
`shard_map` manual region by positive detection — not by swallowing
exceptions — so a genuinely failing constraint still raises.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.plan import CommPlan, HaloBackend, resolve_backend
from repro.core import topology as topo
from repro.core.topology import TopologyConfig
from repro.launch import sharding as shlib

N = 64


def _cfg(kind, **kw):
    kw.setdefault("n_clients", N)
    kw.setdefault("k_out", {"ring": 1, "exponential": 1}.get(kind, 4))
    if kind == "two_tier":
        kw.setdefault("n_pods", 8)
    return TopologyConfig(kind=kind, **kw)


ALL_KINDS = ["ring", "exponential", "kout", "two_tier", "symmetric", "full"]


# ---------------------------------------------------------------------------
# (1) One k_in table.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ALL_KINDS)
def test_k_in_single_source_of_truth(kind):
    cfg = _cfg(kind)
    k_in = topo.family_k_in(cfg)
    assert topo.neighbor_k_max(cfg) == k_in + 1
    if kind in ("ring", "exponential", "kout", "two_tier"):
        assert topo.active_k_in(cfg) == k_in
        plan = CommPlan.build(cfg)
        assert plan.k_in == k_in and plan.k_max == k_in + 1
    # the symmetric mixer overrides every family to the matching graph
    assert topo.family_k_in(cfg, "symmetric") == 2 * cfg.k_out


def test_k_in_matches_sampled_list_shapes():
    """The table IS the slot count of the concrete samplers."""
    key = jax.random.PRNGKey(0)
    for kind in ("ring", "exponential", "kout", "symmetric", "two_tier"):
        cfg = _cfg(kind)
        nl = topo.sample_neighbors(key, cfg)
        if kind == "two_tier":
            # inter list: self slot + k_out cross edges; intra covers the
            # pod's ps - 1 other senders — together the table entry.
            ps = cfg.n_clients // cfg.n_pods
            assert nl.inter.idx.shape[1] == cfg.k_out + 1
            assert topo.family_k_in(cfg) == ps - 1 + cfg.k_out
        else:
            assert nl.idx.shape[1] == topo.neighbor_k_max(cfg)


# ---------------------------------------------------------------------------
# (2) Plan in-neighbors == dense operator support (every family, every hop).
# ---------------------------------------------------------------------------

def _dense_support(P):
    """Off-diagonal nonzero columns per row of a densified operator."""
    P = np.asarray(P)
    return [
        set(np.flatnonzero(P[i]).tolist()) - {i} for i in range(P.shape[0])
    ]


@pytest.mark.parametrize("kind,t", [
    ("ring", 0),
    ("exponential", 0),
    ("exponential_cycle", 0),
    ("exponential_cycle", 1),
    ("exponential_cycle", 5),   # wraps past log2(N) hops
    ("kout", 0),
    ("two_tier", 0),
])
def test_plan_in_neighbors_match_dense_support(kind, t):
    """`CommPlan.in_neighbors` over the full active set names exactly the
    senders the densified sampled operator reads — the pager's fault-in
    set and the mixing support can never disagree."""
    tv = kind == "exponential_cycle"
    cfg = _cfg("exponential" if tv else kind, time_varying=tv)
    plan = CommPlan.build(cfg)
    key = jax.random.PRNGKey(7)
    op = topo.sample_neighbors(key, cfg, t=t)
    dense = (
        topo.dense_from_two_tier(op)
        if cfg.kind == "two_tier"
        else topo.dense_from_neighbors(op, N)
    )
    support = _dense_support(dense)
    picks = np.asarray(plan.in_neighbors(key, jnp.arange(N, dtype=jnp.int32), t=t))
    assert picks.shape == (N, plan.k_in)
    for i in range(N):
        assert set(picks[i].tolist()) == support[i], f"row {i}"


# ---------------------------------------------------------------------------
# (3) Static legs cover exactly the shard reads; dynamic capacity bounds.
# ---------------------------------------------------------------------------

def _legs_delivered(plan, shard):
    """Global rows the ShiftLeg transport delivers to `shard`."""
    rows = []
    for leg in plan.legs:
        src = (shard - leg.delta) % plan.n_shards
        rows.extend(src * plan.m + off for off in leg.offsets)
    return set(rows)


@pytest.mark.parametrize("kind,tv", [
    ("ring", False), ("exponential", False), ("exponential", True),
])
@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_static_legs_cover_shard_reads(kind, tv, n_shards):
    cfg = _cfg(kind, time_varying=tv)
    plan = CommPlan.build(cfg, n_shards=n_shards)
    assert plan.static and plan.legs
    hops = (
        range(max(int(np.ceil(np.log2(N))), 1)) if tv else [0]
    )
    for t in hops:
        nl = topo.sample_neighbors(jax.random.PRNGKey(0), cfg, t=t)
        for s in range(n_shards):
            need = set(plan.shard_remote_rows(nl, s).tolist())
            got = _legs_delivered(plan, s)
            assert need <= got, f"t={t} shard {s}: missing {need - got}"
    if not tv:
        # single-hop plans are exact, not just covering
        nl = topo.sample_neighbors(jax.random.PRNGKey(0), cfg)
        for s in range(n_shards):
            assert _legs_delivered(plan, s) == set(
                plan.shard_remote_rows(nl, s).tolist()
            )


@pytest.mark.parametrize("kind", ["kout", "two_tier", "symmetric"])
def test_dynamic_capacity_bounds_sampled_realizations(kind):
    mixer_kind = "symmetric" if kind == "symmetric" else "directed"
    cfg = _cfg(kind)
    plan = CommPlan.build(cfg, n_shards=8, mixer_kind=mixer_kind)
    assert not plan.static
    for seed in range(5):
        op = topo.sample_neighbors(jax.random.PRNGKey(seed), cfg)
        nl = op.inter if cfg.kind == "two_tier" else op
        for s in range(plan.n_shards):
            rows = plan.shard_remote_rows(nl, s)
            # per source shard, distinct requests fit the pair capacity
            for src in range(plan.n_shards):
                lo, hi = src * plan.m, (src + 1) * plan.m
                pair = rows[(rows >= lo) & (rows < hi)]
                assert pair.size <= plan.capacity
        meas = plan.measured_rows(op)
        assert meas["rows_max"] <= plan.halo_rows()


def test_halo_traffic_accounting():
    ring = CommPlan.build(_cfg("ring"), n_shards=8)
    assert ring.halo_rows() == 1                  # one boundary row
    assert ring.request_ints() == 0               # static: no index traffic
    assert ring.allgather_rows() == 7 * 8
    assert ring.halo_bytes(d=100) == 400
    assert ring.allgather_bytes(d=100) == 7 * 8 * 100 * 4
    kout = CommPlan.build(_cfg("kout"), n_shards=8)
    assert kout.halo_rows() == 7 * kout.capacity
    assert kout.request_ints() == 7 * kout.capacity
    one = CommPlan.build(_cfg("kout"), n_shards=1)
    assert one.halo_rows() == 0 and one.allgather_rows() == 0


def test_plan_store_side_matches_topology():
    cfg = _cfg("kout")
    plan = CommPlan.build(cfg)
    assert plan.pageable
    from repro.store import paging

    assert plan.closure_bound(16) == paging.closure_bound(
        N, 16, topo.active_k_in(cfg)
    )
    sym = CommPlan.build(_cfg("symmetric"))
    assert not sym.pageable
    with pytest.raises(ValueError, match="no active-set"):
        sym.closure_bound(16)


def test_build_rejects_indivisible_shards():
    with pytest.raises(ValueError, match="divisible"):
        CommPlan.build(_cfg("ring"), n_shards=7)


# ---------------------------------------------------------------------------
# (4) The dispatch rule.
# ---------------------------------------------------------------------------

def _mesh1(axis="clients"):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), (axis,))


def test_resolve_backend_without_mesh():
    cfg = _cfg("ring")
    assert resolve_backend("auto", True, cfg, "directed") is None
    assert resolve_backend("sparse", True, cfg, "directed") is None
    assert resolve_backend("xla", True, cfg, "directed") == "xla"
    with pytest.raises(ValueError, match="halo"):
        resolve_backend("halo", True, cfg, "directed")
    with pytest.raises(ValueError, match="gossip must be"):
        resolve_backend("bogus", True, cfg, "directed")


def test_resolve_backend_with_mesh():
    cfg = _cfg("ring")
    mesh = _mesh1()
    # dense representation under a mesh: the partitioner needs plain HLO
    assert resolve_backend("dense", False, cfg, "directed", mesh) == "xla"
    assert resolve_backend("xla", True, cfg, "directed", mesh) == "xla"
    b = resolve_backend("halo", True, cfg, "directed", mesh)
    assert isinstance(b, HaloBackend) and b.axis == "clients"
    # auto on a single-shard axis: nothing crosses shards, all-gather is free
    assert resolve_backend("auto", True, cfg, "directed", mesh) == "xla"
    # a mesh without the bank-row axis is no mesh at all for the bank
    assert resolve_backend("auto", True, cfg, "directed",
                           _mesh1("data")) is None


# ---------------------------------------------------------------------------
# (5) Manual-region detection: constrain skips by detection, not except.
# ---------------------------------------------------------------------------

def test_in_manual_region_detection():
    from jax.sharding import PartitionSpec as P

    mesh = _mesh1("data")
    assert shlib.in_manual_region(mesh) is False
    seen = {}

    def body(x):
        seen["inside"] = shlib.in_manual_region(mesh)
        return shlib.constrain(x + 1.0, ("batch", "embed"))  # must not raise

    with shlib.use_mesh(mesh):
        out = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                            out_specs=P("data"))(jnp.ones((4, 8)))
        assert seen["inside"] is True
        # outside the region the constraint applies normally
        y = shlib.constrain(jnp.ones((4, 8)), ("batch", "embed"))
    np.testing.assert_array_equal(np.asarray(out), 2.0)
    np.testing.assert_array_equal(np.asarray(y), 1.0)
    assert shlib.in_manual_region(mesh) is False


def test_constrain_spmd_axis_vmap_still_constrained():
    """`vmap(spmd_axis_name=...)` is NOT a manual region — constraints
    there are valid, wanted, and must keep flowing to the partitioner."""
    mesh = _mesh1("data")
    with shlib.use_mesh(mesh):
        out = jax.vmap(
            lambda x: shlib.constrain(x * 2.0, ("embed",)),
            spmd_axis_name="data",
        )(jnp.ones((4, 8)))
    np.testing.assert_array_equal(np.asarray(out), 2.0)


def test_constrain_propagates_real_errors(monkeypatch):
    """The old implementation swallowed EVERY exception from
    with_sharding_constraint; a malformed constraint must now raise."""
    mesh = _mesh1("data")

    def boom(*a, **k):
        raise ValueError("malformed sharding constraint")

    with shlib.use_mesh(mesh):
        monkeypatch.setattr(jax.lax, "with_sharding_constraint", boom)
        with pytest.raises(ValueError, match="malformed"):
            shlib.constrain(jnp.ones((4, 8)), ("batch", "embed"))
