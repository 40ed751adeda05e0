"""Chip smoke run: DFedSGPSM's training round on a TPU, through the library's
main path (``FLTrainer`` -> ``RoundProgram.run_superstep`` -> the SAM-momentum
solver with ``fused_update_bank`` -> push-sum gossip through the Pallas
kernels), at the paper models' full widths with seeded random weights.

  python chip_smoke.py             # one chip: phases A and B
  python chip_smoke.py --chips 4   # four chips: the row-sharded bank only

Phase A (dense): mnist_2nn (D=199,210), 16 clients, Dirichlet 0.3, kout
k_out=4 -> ``gossip_matmul``.  Phase B (sparse): cifar_cnn (D=1,756,426),
100 clients, Dirichlet 0.3, kout k_out=10 -> ``gossip_gather``.  Each phase
checks that the compiled round holds the phase's Mosaic kernels, that the
loss is finite and falls, that push-sum mass stays n, and that one mix on
the chip matches ``repro.kernels.ref`` on the same inputs.

``--chips 4`` runs mnist_2nn at n=512 on a ``clients`` mesh (ring: static
halo over ``ppermute``; kout k_out=10: the ``all_to_all`` halo) against the
same program unmeshed on one device, round by round from the same state, at
``tests/test_sharded.py``'s tolerances, with push-sum mass checked every
round.

The last stdout line is one JSON object naming the device; it is printed
only when every check passed.
Without a TPU the script exits nonzero before any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu logs under /tmp by default; keep the run's writes inside the
# checkout and the compile cache.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

REL_TOL = 1e-5  # one mix vs repro.kernels.ref, relative to max |ref|
SHARD_TOL = 1e-5  # tests/test_sharded.py: params / push-sum weights
MASS_TOL = 1e-3  # per client: |sum w - n| <= MASS_TOL * n


class SmokeFailure(AssertionError):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


def kernels_in(hlo_text: str) -> set:
    """Names of the Mosaic kernels (``tpu_custom_call``) in compiled HLO;
    every ``pallas_call`` of the repo is named after its kernel."""
    return {
        m.group(1)
        for m in re.finditer(r"%([A-Za-z_]+)(?:\.\d+)? = [^\n]*"
                             r'custom_call_target="tpu_custom_call"', hlo_text)
    }


def rel_err(got, want) -> float:
    import jax.numpy as jnp

    scale = float(jnp.max(jnp.abs(want)))
    return float(jnp.max(jnp.abs(got - want))) / max(scale, 1e-30)


def client_setting(dataset, n, n_train, n_test, pad_to, seed):
    import jax.numpy as jnp

    from repro.data.dirichlet import dirichlet_partition, stack_client_data
    from repro.data.synthetic import make_dataset

    train, test = make_dataset(dataset, n_train, n_test, seed=seed)
    parts = dirichlet_partition(train["y"], n, alpha=0.3, seed=seed)
    cdata = stack_client_data(train, parts, pad_to=pad_to)
    return ({k: jnp.asarray(v) for k, v in cdata.items()},
            {k: jnp.asarray(v) for k, v in test.items()})


def train_phase(label, *, dataset, model, n, k_out, n_train, pad_to,
                kernels, sparse, rounds, seed, lr=0.1):
    """One single-chip phase through ``FLTrainer.fit``; raises on failure."""
    import jax

    from repro.core import FLTrainer, TopologyConfig, make_algo, pushsum
    from repro.kernels import ref

    cdata, testj = client_setting(dataset, n, n_train, 1000, pad_to, seed)
    algo = make_algo("dfedsgpsm", local_steps=5, batch_size=32, lr=lr)
    topo = TopologyConfig(kind="kout", n_clients=n, k_out=k_out)
    tr = FLTrainer(model.loss, model.init, cdata, algo, topo, seed=seed)
    prog = tr.program
    say(f"phase {label}: {model.name} D={prog.spec.dim} n={n} kout "
        f"k_out={k_out} dfedsgpsm K=5 batch=32 lr={lr} "
        f"sparse_mix={prog.sparse_mix}")
    check(prog.sparse_mix == sparse,
          f"density rule picked sparse_mix={prog.sparse_mix}, want {sparse}")

    # The round as compiled for the chip holds the phase's Mosaic kernels.
    hlo = jax.jit(prog.step).lower(tr.state, prog.data).compile().as_text()
    found = kernels_in(hlo)
    say(f"phase {label}: Mosaic kernels in the compiled round: "
        f"{sorted(found)}")
    check(kernels <= found, f"compiled round lacks {sorted(kernels - found)}")

    # Supersteps with in-scan eval: the first compiles, the second reuses.
    half = rounds // 2
    hist = tr.fit(half, test_data=testj, eval_every=half, superstep=half)
    hist += tr.fit(rounds - half, test_data=testj, eval_every=half,
                   superstep=rounds - half)
    losses = [h["loss"] for h in hist]
    evals = [h["test_loss"] for h in hist if "test_loss" in h]
    say(f"phase {label}: train loss per round {losses}; in-scan test loss "
        f"{evals}")
    check(all(map(_finite, losses)), "non-finite train loss")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    check(len(evals) == 2 and all(map(_finite, evals)),
          f"in-scan eval missing or non-finite: {evals}")
    mass = float(tr.state.w.sum())
    say(f"phase {label}: push-sum mass {mass!r} (n={n})")
    check(abs(mass - n) <= MASS_TOL * n, f"push-sum mass {mass} != {n}")

    # One mix on the chip vs the reference on the same inputs.
    P = prog.mixing_matrix(jax.random.PRNGKey(seed + 1), tr.state)
    X = tr.state.params
    mix = jax.jit(pushsum.gossip_bank)
    found = kernels_in(mix.lower(P, X).compile().as_text())
    check(kernels - {"fused_update_bank"} <= found,
          f"mix did not run the gossip kernel: {sorted(found)}")
    got = mix(P, X)
    if sparse:
        # gossip_gather_ref materializes (n, k_max, D); compare by column
        # panels of the same bank.
        ref_fn = jax.jit(ref.gossip_gather_ref)
        step = 1 << 17
        err = max(
            rel_err(got[:, a:a + step], ref_fn(P.idx, P.wgt, X[:, a:a + step]))
            for a in range(0, X.shape[1], step)
        )
    else:
        err = rel_err(got, jax.jit(ref.gossip_matmul_ref)(P, X))
    say(f"phase {label}: one mix vs repro.kernels.ref: max rel err {err!r}")
    check(err <= REL_TOL, f"mix rel err {err} > {REL_TOL}")


def _finite(x) -> bool:
    return x == x and abs(x) != float("inf")


def phase_a(seed):
    from repro.models.small import mnist_2nn

    train_phase("A", dataset="mnist", model=mnist_2nn(), n=16, k_out=4,
                n_train=4000, pad_to=256,
                kernels={"fused_update_bank", "gossip_matmul"}, sparse=False,
                rounds=6, seed=seed)


def phase_b(seed):
    from repro.models.small import cifar_cnn

    # At the default lr=0.1 (momentum 0.9) the CNN diverges within the
    # first round on the synthetic CIFAR-shaped data, on the CPU as on the
    # chip; lr=0.01 trains.
    train_phase("B", dataset="cifar10", model=cifar_cnn(), n=100, k_out=10,
                n_train=50_000, pad_to=512,
                kernels={"fused_update_bank", "gossip_gather"}, sparse=True,
                rounds=4, seed=seed, lr=0.01)


def phase_sharded(seed, n=512):
    """Row-sharded bank on the ``clients`` mesh == unmeshed on one device.

    Each round starts both programs from the same state (the sharded
    run's, copied to one device) and compares their outputs.  Whole
    trajectories are not compared: the chip does not compute a 128-row
    shard and the 512-row bank bitwise alike, and a ReLU network turns a
    last-bit difference into a much larger one (on the CPU, a 1e-7 nudge
    to the params grows to 7e-5-2e-3 after one 5-step round).  ``sgp``
    (one plain gradient step per round), as in ``tests/test_sharded.py``'s
    halo case, keeps the round's own arithmetic from amplifying it.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import TopologyConfig, make_algo, make_program
    from repro.launch.mesh import make_clients_mesh
    from repro.models.small import mnist_2nn

    rounds = 3
    model = mnist_2nn()
    cdata, _ = client_setting("mnist", n, n * 64, 100, 64, seed)
    algo = make_algo("sgp", batch_size=32)
    mesh = make_clients_mesh()
    one_device = jax.devices()[0]
    cases = [
        ("ring", TopologyConfig(kind="ring", n_clients=n, k_out=1), "auto"),
        ("kout", TopologyConfig(kind="kout", n_clients=n, k_out=10), "halo"),
    ]
    for name, topo, gossip in cases:
        sh = make_program(model.loss, model.init, cdata, algo, topo,
                          gossip=gossip, mesh=mesh)
        ref = make_program(model.loss, model.init, cdata, algo, topo,
                           gossip="sparse")
        backend = sh.mixer.backend
        check(type(backend).__name__ == "HaloBackend",
              f"{name}: gossip backend {backend!r} is not the halo exchange")
        say(f"sharded {name}: n={n} D={sh.spec.dim} over mesh "
            f"{dict(mesh.shape)}, halo plan static={backend.plan.static}")
        s_sh = sh.init(jax.random.PRNGKey(seed))
        step_sh = jax.jit(sh.step)
        step_ref = jax.jit(ref.step)
        for r in range(rounds):
            s_ref, m_ref = step_ref(jax.device_put(s_sh, one_device),
                                    ref.data)
            s_sh, m_sh = step_sh(s_sh, sh.data)
            mass = float(jnp.sum(s_sh.w))
            perr, werr = (
                float(jnp.max(jnp.abs(jax.device_get(a) - jax.device_get(b))))
                for a, b in ((s_sh.params, s_ref.params), (s_sh.w, s_ref.w)))
            say(f"sharded {name} round {r}: loss {float(m_sh['loss'])!r} vs "
                f"{float(m_ref['loss'])!r}, max abs err params {perr!r} "
                f"w {werr!r}, mass {mass!r}")
            check(abs(mass - n) < MASS_TOL, f"{name} round {r}: mass {mass}")
            check(perr < SHARD_TOL, f"{name} round {r}: params err {perr}")
            check(werr < SHARD_TOL, f"{name} round {r}: w err {werr}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.runtime import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache {cache_dir}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly "
              f"{args.chips} devices, found {len(devices)}", file=sys.stderr)
        return 2

    phases = ([("sharded", phase_sharded)] if args.chips == 4
              else [("A", phase_a), ("B", phase_b)])
    failed = []
    for label, fn in phases:
        try:
            fn(args.seed)
        except Exception:  # report every phase, then fail the run
            traceback.print_exc()
            failed.append(label)
            say(f"phase {label}: FAIL")
            continue
        say(f"phase {label}: PASS")
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
