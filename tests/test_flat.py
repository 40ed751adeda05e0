"""Flat client-parameter bank: ravel/unravel round-trips, kernel oracles,
bank checkpointing, and — the load-bearing guarantee — exact equivalence of
the flat-bank engine round with the seed pytree path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # graceful tier-1 degradation (see tests/_hyp.py)
    from _hyp import given, settings, st

from repro import checkpoint
from repro.core import FLTrainer, TopologyConfig, make_algo, make_spec
from repro.core import pushsum, topology
from repro.data.dirichlet import dirichlet_partition, stack_client_data
from repro.data.synthetic import make_dataset
from repro.kernels import ops, ref
from repro.models.small import mnist_2nn

N_CLIENTS = 8


# ---------------------------------------------------------------------------
# ravel / unravel round-trips
# ---------------------------------------------------------------------------

_DTYPES = [jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32]


def _random_tree(seed: int, n_leaves: int, rng):
    """A nested mixed-dtype pytree with random leaf shapes."""
    tree, keys = {}, jax.random.split(jax.random.PRNGKey(seed), n_leaves)
    for i, k in enumerate(keys):
        shape = tuple(rng.randint(1, 5) for _ in range(rng.randint(0, 3)))
        dt = _DTYPES[rng.randint(0, len(_DTYPES) - 1)]
        if jnp.issubdtype(dt, jnp.integer):
            # Stay far inside float-exact integer range so the promoted
            # flat storage dtype round-trips losslessly.
            leaf = jax.random.randint(k, shape, -100, 100, jnp.int32).astype(dt)
        else:
            leaf = jax.random.normal(k, shape, dt)
        group = tree.setdefault(f"g{i % 3}", {})
        group[f"leaf{i}"] = leaf
    return tree


@given(st.integers(0, 999), st.integers(1, 9))
@settings(max_examples=15, deadline=None)
def test_ravel_unravel_roundtrip(seed, n_leaves):
    import random

    rng = random.Random(seed)
    tree = _random_tree(seed, n_leaves, rng)
    spec = make_spec(tree)
    row = spec.ravel(tree)
    assert row.shape == (spec.dim,)
    back = spec.unravel(row)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_ravel_unravel_stacked_roundtrip():
    tree = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (3, 4)),
        "b": jnp.arange(5, dtype=jnp.bfloat16),
    }
    spec = make_spec(tree)
    stacked = jax.tree.map(
        lambda x: jnp.stack([x, 2 * x, 3 * x, 4 * x]), tree)
    bank = spec.ravel_stacked(stacked)
    assert bank.shape == (4, spec.dim)
    back = spec.unravel_stacked(bank)
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(back)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))
    # row i of the bank unravels to client i's pytree
    one = spec.unravel(bank[2])
    np.testing.assert_array_equal(np.asarray(one["w"]), np.asarray(3 * tree["w"]))


def test_spec_offsets_are_contiguous():
    tree = {"a": jnp.zeros((2, 3)), "b": jnp.zeros((7,)), "c": jnp.zeros(())}
    spec = make_spec(tree)
    assert spec.offsets[0] == 0
    for o, s, o_next in zip(spec.offsets, spec.sizes, spec.offsets[1:]):
        assert o + s == o_next
    assert spec.offsets[-1] + spec.sizes[-1] == spec.dim == 2 * 3 + 7 + 1


# ---------------------------------------------------------------------------
# banked kernels vs oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d", [(3, 17), (8, 256), (5, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_bank_matches_ref(n, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    X = jax.random.normal(ks[0], (n, d), dtype)
    V = jax.random.normal(ks[1], (n, d), jnp.float32)
    G = jax.random.normal(ks[2], (n, d), dtype)
    w = jax.random.uniform(ks[3], (n,), jnp.float32, 0.5, 2.0)
    got = ops.fused_update_bank(X, V, G, 0.9, 0.05, w)
    want = ref.fused_update_bank_ref(X, V, G, 0.9, 0.05, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol)


@pytest.mark.parametrize("n,d,block_n,block_d", [
    (5, 300, 8, 128), (100, 1000, 8, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_bank_blocked_grid_path(n, d, block_n, block_d, dtype):
    """Force the multi-block pl.pallas_call route over the unpadded bank:
    neither block size divides it, so the last row and column tiles are
    ragged."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    X = jax.random.normal(ks[0], (n, d), dtype)
    V = jax.random.normal(ks[1], (n, d))
    G = jax.random.normal(ks[2], (n, d), dtype)
    w = jax.random.uniform(ks[3], (n,), jnp.float32, 0.5, 2.0)
    got = ops.fused_update_bank(X, V, G, 0.5, 0.1, w, block_n=block_n,
                                block_d=block_d)
    want = ref.fused_update_bank_ref(X, V, G, 0.5, 0.1, w)
    rtol, atol = (2e-2, 2e-2) if dtype == jnp.bfloat16 else (1e-5, 1e-6)
    for a, b in zip(got, want):
        assert a.shape == b.shape == (n, d) and a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=rtol, atol=atol)


def test_gossip_bank_matches_pytree_gossip():
    n, shapes = 6, ((3, 4), (7,))
    key = jax.random.PRNGKey(0)
    tree = {
        f"p{i}": jax.random.normal(k, (n,) + s)
        for i, (k, s) in enumerate(zip(jax.random.split(key, 2), shapes))
    }
    spec = make_spec(jax.tree.map(lambda x: x[0], tree))
    P = topology.sample_kout(jax.random.PRNGKey(1), n, 2)
    bank = spec.ravel_stacked(tree)
    mixed_bank = spec.unravel_stacked(pushsum.gossip_bank(P, bank))
    mixed_tree = pushsum.gossip(P, tree)
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(mixed_bank[k]), np.asarray(mixed_tree[k]),
            rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# engine equivalence: flat bank vs seed pytree path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setting():
    train, _ = make_dataset("mnist", 1200, 100, seed=0)
    parts = dirichlet_partition(train["y"], N_CLIENTS, alpha=0.3, seed=0)
    cdata = stack_client_data(train, parts, pad_to=128)
    return mnist_2nn(), {k: jnp.asarray(v) for k, v in cdata.items()}


@pytest.mark.parametrize("name", ["dfedsgpsm", "dfedavgm", "fedavg"])
def test_flat_round_matches_pytree_round(setting, name):
    model, cdata = setting
    algo = make_algo(name, local_steps=3, batch_size=32)
    topo = TopologyConfig(kind="kout", n_clients=N_CLIENTS, k_out=2)

    def trainer(flat):
        return FLTrainer(model.loss, model.init, cdata, algo, topo, seed=0,
                         participation=0.25, flat=flat)

    trf, trp = trainer(True), trainer(False)
    for _ in range(3):
        mf = trf.run_round()
        mp = trp.run_round()
        np.testing.assert_allclose(
            float(mf["loss"]), float(mp["loss"]), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            float(mf["acc"]), float(mp["acc"]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(trf.state.w), np.asarray(trp.state.w), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(trf.average_model()),
                    jax.tree.leaves(trp.average_model())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flat_debiased_models_match(setting):
    model, cdata = setting
    algo = make_algo("dfedsgpsm", local_steps=2, batch_size=32)
    topo = TopologyConfig(kind="kout", n_clients=N_CLIENTS, k_out=2)
    trf = FLTrainer(model.loss, model.init, cdata, algo, topo, seed=0,
                    participation=0.25, flat=True)
    trp = FLTrainer(model.loss, model.init, cdata, algo, topo, seed=0,
                    participation=0.25, flat=False)
    trf.run_round()
    trp.run_round()
    for a, b in zip(jax.tree.leaves(trf.debiased_models()),
                    jax.tree.leaves(trp.debiased_models())):
        assert a.shape == b.shape  # client-stacked layout preserved
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        float(trf.consensus_error()), float(trp.consensus_error()),
        rtol=1e-3, atol=1e-6)


def test_flat_momentum_bank_populated(setting):
    model, cdata = setting
    algo = make_algo("dfedsgpsm", local_steps=2, batch_size=32)
    topo = TopologyConfig(kind="kout", n_clients=N_CLIENTS, k_out=2)
    tr = FLTrainer(model.loss, model.init, cdata, algo, topo, seed=0,
                   participation=0.25, flat=True)
    assert tr.state.mom.shape == (N_CLIENTS, tr.spec.dim)
    assert not np.any(np.asarray(tr.state.mom))
    tr.run_round()
    assert np.any(np.asarray(tr.state.mom))  # end-of-round momentum stored


# ---------------------------------------------------------------------------
# time-varying exponential graphs actually vary with the round (bug fix)
# ---------------------------------------------------------------------------

def test_exponential_cycle_matrices():
    cyc = topology.exponential_cycle(16)
    assert cyc.shape == (4, 16, 16)
    for t in range(4):
        np.testing.assert_allclose(
            np.asarray(cyc[t]), np.asarray(topology.directed_exponential(16, t)))


def test_exponential_topology_varies_across_rounds(setting):
    model, cdata = setting
    algo = make_algo("dfedsgpsm", local_steps=1, batch_size=16)
    topo = TopologyConfig(kind="exponential", n_clients=N_CLIENTS, k_out=1)
    tr = FLTrainer(model.loss, model.init, cdata, algo, topo, seed=0, flat=True)
    key = jax.random.PRNGKey(0)
    hops = tr._exp_cycle.shape[0]
    mats = [
        np.asarray(tr._mixing(key, tr.state._replace(round=jnp.int32(t))))
        for t in range(hops)
    ]
    for t in range(1, hops):
        assert not np.allclose(mats[0], mats[t]), "graph must vary with round"
    np.testing.assert_allclose(
        mats[1], np.asarray(topology.directed_exponential(N_CLIENTS, 1)))
    # the union over one cycle restores Assumption 1 connectivity
    assert topology.union_strongly_connected(mats)
    tr.run_round()  # and the round stays jittable end-to-end
    assert np.isclose(float(tr.state.w.sum()), N_CLIENTS, atol=1e-3)


# ---------------------------------------------------------------------------
# flat-bank checkpointing: one array + offsets
# ---------------------------------------------------------------------------

def test_bank_checkpoint_roundtrip(tmp_path):
    tree = {"layer": {"w": jnp.ones((2, 3)), "b": jnp.zeros((3,))}}
    spec = make_spec(tree)
    bank = jax.random.normal(jax.random.PRNGKey(0), (4, spec.dim))
    w = jnp.full((4,), 1.25)
    path = checkpoint.save_bank(str(tmp_path), 7, bank, spec, extra={"w": w})
    assert checkpoint.latest_checkpoint(str(tmp_path)) == path
    got, extra, meta = checkpoint.restore_bank(path, spec=spec)
    np.testing.assert_array_equal(got, np.asarray(bank))
    np.testing.assert_array_equal(extra["w"], np.asarray(w))
    assert meta["dim"] == spec.dim
    assert meta["offsets"] == list(spec.offsets)


def test_bank_checkpoint_v2_row_chunked_roundtrip(tmp_path):
    """Format v2: the bank and every bank-shaped extra stream into the
    archive as row chunks (the writer never holds the (n, D) bank whole on
    the host); reassembly is exact across chunk boundaries, (n,) vectors
    and scalars stay whole members."""
    tree = {"layer": {"w": jnp.ones((2, 3)), "b": jnp.zeros((3,))}}
    spec = make_spec(tree)
    n = 1000
    bank = jax.random.normal(jax.random.PRNGKey(0), (n, spec.dim))
    mom = jax.random.normal(jax.random.PRNGKey(1), (n, spec.dim))
    w = jnp.linspace(0.5, 1.5, n)
    path = checkpoint.save_bank(
        str(tmp_path), 3, bank, spec,
        extra={"mom": mom, "w": w, "round": jnp.int32(3)}, chunk_rows=128)
    with np.load(path) as data:
        chunks = [f for f in data.files if f.startswith("__bank_c")]
        assert len(chunks) == 8  # ceil(1000 / 128)
        assert "extra_mom_c00000" in data.files  # bank-shaped: chunked
        assert "extra_w" in data.files           # (n,) vector: whole
    got, extra, meta = checkpoint.restore_bank(path, spec=spec)
    assert meta["format"] == 2 and meta["bank_chunks"] == 8
    np.testing.assert_array_equal(got, np.asarray(bank))
    np.testing.assert_array_equal(extra["mom"], np.asarray(mom))
    np.testing.assert_array_equal(extra["w"], np.asarray(w))
    assert int(extra["round"]) == 3


def test_bank_checkpoint_v1_loads_transparently(tmp_path):
    """A legacy monolithic ``__bank__`` checkpoint (pre-chunking) restores
    through the same reader, extras included — old run directories stay
    resumable after the format bump."""
    import json

    from repro.checkpoint import io as ckpt_io

    spec = make_spec({"a": jnp.zeros((3,))})
    bank = np.arange(12, dtype=np.float32).reshape(4, 3)
    p = str(tmp_path / "ckpt_0.npz")
    np.savez(p, __bank__=bank,
             __bank_meta__=np.array(json.dumps(ckpt_io._spec_meta(spec))),
             extra_w=np.full((4,), 1.25, np.float32))
    got, extra, meta = checkpoint.restore_bank(p, spec=spec)
    np.testing.assert_array_equal(got, bank)
    np.testing.assert_array_equal(extra["w"], np.full((4,), 1.25,
                                                      np.float32))
    assert meta.get("format", 1) != 2


def test_bank_checkpoint_central_row(tmp_path):
    """A central (D,) row (FedAvg server state) rides the same writer as a
    single whole chunk."""
    spec = make_spec({"a": jnp.zeros((5,))})
    row = jnp.arange(5, dtype=jnp.float32)
    path = checkpoint.save_bank(str(tmp_path), 0, row, spec)
    got, _, meta = checkpoint.restore_bank(path, spec=spec)
    np.testing.assert_array_equal(got, np.asarray(row))
    assert meta["rows"] == 0


def test_bank_checkpoint_structure_mismatch(tmp_path):
    spec = make_spec({"a": jnp.zeros((3,))})
    other = make_spec({"a": jnp.zeros((4,))})
    path = checkpoint.save_bank(str(tmp_path), 0, jnp.zeros((2, 3)), spec)
    with pytest.raises(ValueError):
        checkpoint.restore_bank(path, spec=other)
    with pytest.raises(ValueError):
        checkpoint.restore_bank(
            checkpoint.save(str(tmp_path), 1, {"a": jnp.zeros(3)}))
