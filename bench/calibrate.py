"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

  python3 bench/calibrate.py --workload <name> --seeds 11,12,... \\
      [--control-seeds 11,12,13] [--controls control,bank_bf16,half_batch]

For every seed: the program's first rounds, built and driven exactly as
``run.py``'s set-up drives them, against the plain reference from the
same seed.  For each control seed also, each against the reference:

  control     the reference computed in bfloat16, put in the program's place
  bank_bf16   the program's own lower-precision path: its bank rows stored
              in bfloat16 (``FLTrainer(bank_dtype=bfloat16)``)
  half_batch  the planted fault: the reference with each minibatch's loss
              taken over its first half

(``--controls`` picks which).  A state left unchanged needs no run: it
reads 1 on ``grad`` and ``change``.  One JSON line per reading goes to
stdout, with the readings it was compared from under ``raw`` (the
reference's on the program's line).
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if sys.path and os.path.abspath(sys.path[0] or ".") == BENCH:
    sys.path[0] = ROOT

from bench import compare, harness, run  # noqa: E402


CONTROLS = ("control", "bank_bf16", "half_batch")


def _program(cell, seed, devices, bank_dtype=None):
    inp = run.build(cell, seed, devices, bank_dtype=bank_dtype)
    t0 = time.perf_counter()
    prog, _ = run.first_rounds(inp, cell.traffic)
    first_s = time.perf_counter() - t0
    inp.trainer = None
    gc.collect()
    return inp, prog, first_s


def _raw(readings):
    return {k: np.asarray(v).tolist() for k, v in readings.items()}


def readings(cell, seed, devices, controls=()):
    inp, prog, first_s = _program(cell, seed, devices)
    t0 = time.perf_counter()
    ref = run.reference_readings(inp, cell)
    ref_s = time.perf_counter() - t0
    out = [{"seed": seed, "who": "program", "first_rounds_s": first_s,
            "reference_s": ref_s, **compare.gaps(prog, ref),
            "raw": _raw(prog), "raw_reference": _raw(ref)}]
    for who in controls:
        if who == "control":
            other = run.reference_readings(inp, cell, control=True)
        elif who == "half_batch":
            other = run.reference_readings(inp, cell, fault="half_batch")
        else:
            import jax.numpy as jnp

            other = _program(cell, seed, devices, bank_dtype=jnp.bfloat16)[1]
        out.append({"seed": seed, "who": who, **compare.gaps(other, ref),
                    "raw": _raw(other)})
    return out


def main(argv=None, *, root=ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    args = ap.parse_args(argv)
    cell = harness.resolve(root, args.workload)
    devices = run.start_jax(root)
    if devices is None:
        return 2
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    controls = [c for c in args.controls.split(",") if c]
    for c in controls:
        if c not in CONTROLS:
            ap.error(f"--controls: {c!r} is not one of {CONTROLS}")
    for seed in seeds:
        for rec in readings(cell, seed, devices,
                            controls if seed in control_seeds else ()):
            print(json.dumps(rec), flush=True)
    print(json.dumps({"workload": args.workload, "device":
                      devices[0].device_kind,
                      "seconds": time.time() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
