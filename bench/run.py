"""One run of one benchmark cell.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is resolved by name from ``BENCHMARK.json`` (``bench/harness.py``).
Set-up makes the inputs and the initial weights from the seed on the
device and builds the program's ``FLTrainer``.  It drives that trainer
through the rounds the check follows one round a call,
``FLTrainer.fit(1, test, eval_every=E, superstep=1)``, reading its state
after rounds 1, 3 and the first eval (``check_rounds``), then makes one
call of the window, ``FLTrainer.fit(R, test, eval_every=E, superstep=R)``,
so that both executables are compiled or read from the compile cache
before the window opens.  The window repeats the window's call on the
same trainer until ``--seconds`` have passed; every call ends in the
history's transfer to the host.  After the window the program is freed and
the plain reference (``bench/reference.py``) follows the same rounds from
the same seed; the numbers compared (``bench/compare.py``) and their
limits (``bench/limits/<workload>.json``) decide ``correct``.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
the per-layer metrics that ``bench/metrics/<name>.py`` read from it.

Without a TPU, or with another number of chips than the cell asks for,
the run exits nonzero and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0] or ".") == BENCH:
    sys.path[0] = ROOT
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import compare, harness  # noqa: E402

MASS_TOL = 1e-3  # push-sum mass: |sum w - n| <= MASS_TOL * n


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _compile_counter():
    """Counts the backend compiles and compile-cache reads of the run."""
    import jax

    counter = types.SimpleNamespace(events=0)

    def listen(event, *_args, **_kw):
        if "backend_compile" in event or "cache_retrieval" in event:
            counter.events += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return counter


def build(cell, seed, devices, bank_dtype=None):
    """Inputs, initial weights and the program's trainer for one seed
    (``bank_dtype``: the program's own lower-precision bank, for the
    control in ``bench/calibrate.py``)."""
    import jax

    from bench import data
    from repro.core import FLTrainer, TopologyConfig, make_algo
    from repro.models.small import get_model

    cfg, trf = cell.config, cell.traffic
    ds, fed, alg = cfg["dataset"], cfg["federation"], cfg["algorithm"]
    ref_model = harness.load_module(cell.model_path, cfg["model"])
    t0 = time.perf_counter()
    clients, test = data.client_inputs(seed, cfg)
    params0 = jax.jit(lambda k: ref_model.init(k, cfg))(
        data.seed_key(seed, 2))
    jax.block_until_ready((clients, params0))
    t_inputs = time.perf_counter() - t0
    model = get_model(cfg["model"], ds["n_classes"], tuple(ds["shape"]))
    algo = make_algo(alg["name"], local_steps=alg["local_steps"],
                     batch_size=alg["batch_size"], rho=alg["rho"],
                     alpha=alg["momentum"], lr=alg["lr"],
                     lr_decay=alg["lr_decay"])
    topo = TopologyConfig(kind=trf["topology"]["kind"],
                          n_clients=fed["n_clients"],
                          k_out=trf["topology"]["k_out"],
                          time_varying=trf["topology"]["time_varying"])
    mesh = None
    if cell.chips > 1:
        from repro.launch.mesh import make_clients_mesh

        mesh = make_clients_mesh(len(devices))
    prog_seed = data.sub_seed(seed, "program")
    trainer = FLTrainer(model.loss, lambda _key: params0, clients, algo, topo,
                        seed=prog_seed, gossip=trf["gossip"], mesh=mesh,
                        bank_dtype=bank_dtype)
    log(f"set-up: inputs and weights {t_inputs:.3f}s, trainer "
        f"{time.perf_counter() - t0 - t_inputs:.3f}s")
    return types.SimpleNamespace(
        trainer=trainer, clients=clients, test=test, params0=params0,
        ref_model=ref_model, prog_seed=prog_seed, norms=None, x0=None)


def check_rounds(traffic):
    """The rounds the check follows, and the rounds after which it reads
    the state: the first gradient after round 1, the change of the bank
    after round 3, and the end, which covers the first in-scan eval."""
    return max(3, traffic["eval_every"]), 1, 3


def state_readings(inp):
    """Host numbers of the trainer's current state: per-leaf norms of the
    bank's change since the initial weights and of the last round's
    momentum, each client's loss in the last round, the push-sum
    weights."""
    import jax
    import jax.numpy as jnp

    if inp.norms is None:
        leaves = jax.tree.leaves(inp.params0)
        sizes = [int(x.size) for x in leaves]
        if list(inp.trainer.program.spec.sizes) != sizes:
            raise RuntimeError(f"bank layout {inp.trainer.program.spec.sizes}"
                               f" is not the weights' leaves {sizes}")
        offs = [sum(sizes[:i]) for i in range(len(sizes))]
        inp.x0 = jnp.concatenate([x.reshape(-1) for x in leaves])

        @jax.jit
        def norms(X, V, x0):
            def per_leaf(A):
                return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                    A[:, o:o + s]))) for o, s in zip(offs, sizes)])

            return (per_leaf(X.astype(jnp.float32) - x0[None, :]),
                    per_leaf(V.astype(jnp.float32)))

        inp.norms = norms
    st = inp.trainer.state
    dx, v = jax.device_get(inp.norms(st.params, st.mom, inp.x0))
    return {"dx": dx, "v": v, "client_losses": jax.device_get(st.losses),
            "w": jax.device_get(st.w).astype("float32")}


def first_rounds(inp, traffic):
    """Drive the trainer through the rounds the check follows, one round a
    call of ``FLTrainer.fit`` (the window's step in a one-round scan, with
    the window's eval cadence), reading its state where the check needs
    it."""
    last, r_grad, r_change = check_rounds(traffic)
    losses, test_loss, out, done, ok = [], float("nan"), {}, 0, True
    while done < last:
        hist = inp.trainer.fit(1, test_data=inp.test,
                               eval_every=traffic["eval_every"], superstep=1)
        ok = ok and superstep_ok(hist)
        done += len(hist)
        losses += [h["loss"] for h in hist]
        test_loss = next((h["test_loss"] for h in reversed(hist)
                          if "test_loss" in h), test_loss)
        if done in (r_grad, r_change, last):
            st = state_readings(inp)
            if done == r_grad:
                out.update(v=st["v"], client_losses=st["client_losses"])
            if done == r_change:
                out["dx"] = st["dx"]
            if done == last:
                out["w"] = st["w"]
    out.update(losses=losses, test_loss=test_loss)
    return out, ok


def window_call(inp, traffic):
    """The window's own call: one superstep of the cell's mix through
    ``FLTrainer.fit``, returning its per-round history records."""
    R = traffic["superstep_rounds"]
    return lambda: inp.trainer.fit(R, test_data=inp.test,
                                   eval_every=traffic["eval_every"],
                                   superstep=R)


def reference_readings(inp, cell, **kw):
    """The plain reference over the rounds the check follows, from the
    same seed: its round key is the program's after ``init``, the second
    half of splitting the program's seed key."""
    import jax

    from bench import reference

    key = jax.random.split(jax.random.PRNGKey(inp.prog_seed))[1]
    return reference.run_check(inp.ref_model, cell.config, cell.traffic,
                               check_rounds(cell.traffic), inp.params0, key,
                               inp.clients, inp.test, **kw)


def superstep_ok(hist) -> bool:
    return all(math.isfinite(h["loss"]) for h in hist)


def run(cell, args, devices, require_tpu=True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    peaks = None
    if require_tpu or args.trace:
        from bench.work import peaks_for

        peaks = peaks_for(devices[0].device_kind)
    counter = _compile_counter()
    log(f"set-up: process start to devices {time.time() - T_START:.3f}s")
    trf, fed = cell.traffic, cell.config["federation"]
    n, R = fed["n_clients"], trf["superstep_rounds"]
    inp = build(cell, args.seed, devices)
    tr = inp.trainer
    superstep = window_call(inp, trf)

    t_first = time.perf_counter()
    prog, ok = first_rounds(inp, trf)
    first_s = time.perf_counter() - t_first
    check_n = check_rounds(trf)[0]
    t_warm = time.perf_counter()
    ok = superstep_ok(superstep()) and ok
    warm_s = time.perf_counter() - t_warm
    mass_fn = jax.jit(jnp.sum)
    mass0 = float(mass_fn(tr.state.w))
    failed_setup = (0 if ok and abs(mass0 - n) <= MASS_TOL * n
                    else check_n + R)
    # Start every window at the same point of the garbage collector's
    # cycle: a full collection of this process takes ~100 ms, and where one
    # falls is otherwise left to how set-up went.
    gc.collect()
    setup_s = time.time() - T_START
    log(f"set-up {setup_s:.3f}s (first {check_n} rounds one a call: "
        f"{first_s:.3f}s; one superstep of {R}: {warm_s:.3f}s; compiles or "
        f"cache reads so far {counter.events}); "
        f"sparse_mix={tr.program.sparse_mix}")

    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
    before = counter.events
    oks, rounds = [], 0
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.superstep"):
            hist = superstep()
        oks.append(superstep_ok(hist))
        rounds += R
        if time.perf_counter() - t0 >= args.seconds:
            break
    window_s = time.perf_counter() - t0
    in_window = counter.events - before
    if args.trace:
        jax.profiler.stop_trace()
    # Push-sum mixing keeps whatever mass the bank holds, so a superstep
    # that put it off n leaves it off at the window's end: read it once
    # there, and count every round of the window where it is off.
    mass = float(mass_fn(tr.state.w))
    failed = failed_setup + (
        rounds if abs(mass - n) > MASS_TOL * n
        else R * sum(not ok for ok in oks))
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    log(f"window {window_s:.3f}s, {rounds} rounds, compiles or cache reads "
        f"in the window {in_window}, mass at the end {mass!r}, "
        f"memory peak {peak} bytes")

    # Free the program before the reference runs on the same chip.
    del tr, superstep
    inp.trainer = None
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference_readings(inp, cell)
    log(f"reference {check_rounds(trf)[0]} rounds "
        f"{time.perf_counter() - t_ref:.3f}s")
    numbers = compare.gaps(prog, ref)
    checks, correct = compare.judge(numbers, cell.limits.get("limits", {}))
    correct = correct and failed == 0

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": rounds,
              "failed": int(failed)}
    if args.trace:
        from bench import devtrace

        t = devtrace.from_profile(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            trace=t, rounds=rounds, cell=cell,
            peaks=peaks, chips=len(devices), n=n,
            dim=sum(int(x.size) for x in jax.tree.leaves(inp.params0)),
            itemsize=4, layers=inp.ref_model.layers(cell.config),
            k_max=trf["topology"]["k_out"] + 1)
        metrics = {}
        for entry, reader in cell.per_layer:
            value = reader.read(ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
        busy = [devtrace.busy_ns(t, d) for d in t.devices]
        device["busy_s"] = sum(busy) / max(len(busy), 1) / 1e9
        device["window_s"] = t.window_ns / 1e9
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = compare.breakdown(t)
    else:
        values = {"round_ms": 1e3 * window_s / rounds, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    for k in ("losses", "test_loss", "dx", "v", "client_losses"):
        log(f"program {k}: {np.asarray(prog[k]).tolist()}")
        log(f"reference {k}: {np.asarray(ref[k]).tolist()}")
    for name, v in numbers.items():
        if name not in checks:
            log(f"number {name}: {v!r} (no limit)")
    checks["failed_rounds"] = {"value": int(failed), "limit": 0}
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result


def start_jax(root):
    """Put the program on the path, point JAX's persistent compile cache
    into the checkout (or at ``$JAX_COMPILATION_CACHE_DIR``) and return the
    devices; None where the program is not in the tree."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"bench: the program is not here ({src}/repro); nothing was run")
        return None
    if src not in sys.path:
        sys.path.insert(0, src)
    # libtpu logs under /tmp by default; keep the run's writes inside the
    # checkout, the compile cache and TMPDIR.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # The superstep executable embeds the eval's test set as a constant
    # (123 MB for CIFAR-10's), which a cache capped at a few hundred MB
    # would refuse or evict: allow 4 GiB, or no cap where none is set.
    if 0 <= jax.config.jax_compilation_cache_max_size < 4 * 2**30:
        jax.config.update("jax_compilation_cache_max_size", 4 * 2**30)
    return jax.devices()


def main(argv=None, *, root=ROOT, require_tpu=True) -> int:
    args = parse(argv)
    try:
        cell = harness.resolve(root, args.workload)
    except harness.BenchSpecError as e:
        log(f"bench: {e}")
        return 2
    devices = start_jax(root)
    if devices is None:
        return 2
    if require_tpu and devices[0].platform != "tpu":
        log(f"bench: no TPU (platform {devices[0].platform}); nothing was "
            "measured")
        return 3
    if len(devices) != cell.chips:
        log(f"bench: {args.workload} needs {cell.chips} chips, JAX finds "
            f"{len(devices)}")
        return 3
    result = run(cell, args, devices, require_tpu=require_tpu)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
