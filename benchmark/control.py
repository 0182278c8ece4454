#!/usr/bin/env python3
"""The controls: runs of the timed path with one guarantee broken, which
the comparison that decides ``correct`` has to refuse.

    python3 benchmark/control.py --workload <cell> --control <name> \
        --seeds 11,12,13 [--checks 2]

runs, for each seed, the cell's set-up and ``--checks`` checks with the
control in place, judges them against the plain reference as the
configuration states it, and prints each number compared beside its
limit, then one JSON line with every seed's readings. The benchmark's
own runs never run a control.

* ``fp32``: the visited set keyed by 32-bit fingerprints instead of
  64-bit ones, the narrower key a faster dedup would tempt one into.
  ``fp<bits>`` narrows to any width; the tests use a width at which a
  small model collides.
* ``no_paths``: the program's own ``track_paths=False`` path, where the
  configuration states that discovery paths are tracked.
"""

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@contextlib.contextmanager
def narrow_fingerprints(bits):
    """Every fingerprint the program computes, on the device and on the
    host alike, keeps ``bits`` bits of its low word and none of its high
    word."""
    from stateright_tpu.ops import fingerprint as fp

    orig = fp._fold_lanes

    def narrowed(w, lane, zero, xp):
        lo, _hi = orig(w, lane, zero, xp)
        if bits < 32:
            lo = (lo & xp.uint32((1 << bits) - 1)) | xp.uint32(1 << bits)
        return lo, zero

    fp._fold_lanes = narrowed
    drop_programs()
    try:
        yield
    finally:
        fp._fold_lanes = orig
        drop_programs()


def drop_programs():
    """Forget the compiled programs this process holds, so the next
    check builds them anew (with or without a control in place)."""
    import jax

    from stateright_tpu.checkers import tpu

    tpu._CHUNK_CACHE.clear()
    jax.clear_caches()


def control(name, cfg):
    """``(context manager, engine config)`` of a control."""
    if name.startswith("fp"):
        return narrow_fingerprints(int(name[2:])), cfg
    if name == "no_paths":
        if not cfg["engine"].get("track_paths", True):
            raise SystemExit("no_paths: the configuration tracks no paths")
        return (contextlib.nullcontext(),
                dict(cfg, engine=dict(cfg["engine"], track_paths=False)))
    raise SystemExit(f"unknown control {name!r}")


def run_control(workload, name, seeds, checks, *, require_tpu=True,
                spec=None, extra=None):
    """``{seed: compared}`` for the control, each seed's checks judged
    against the configuration as it states itself."""
    import harness
    import verdict

    spec = spec or harness.bench_spec()
    cell = harness.find_cell(spec, workload)
    cfg = harness.load_config(cell["config"], extra)
    harness.device_info(cell["chips"], require_tpu)
    system = harness.system_module(cfg, extra)
    ref_mod = harness.reference_module(cfg, extra)
    ctx, run_cfg = control(name, cfg)
    out = {}
    with ctx:
        # a closed loop of one client: every seed sends the same checks
        for seed in seeds:
            done = [harness.run_check(system, run_cfg)
                    for _ in range(checks)]
            judged = verdict.judge(cfg, done, ref_mod, system)
            out[seed] = dict(correct=judged["correct"],
                             compared=judged["compared"],
                             errors=[c["error"] for c in done if c["error"]])
    return out


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--checks", type=int, default=2)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = run_control(args.workload, args.control, seeds, args.checks)
    for seed, r in out.items():
        for k, item in r["compared"].items():
            print(f"control {args.control} seed {seed} {k}: "
                  f"{item['value']} (limit {item['limit']})",
                  file=sys.stderr)
    print(json.dumps(dict(workload=args.workload, control=args.control,
                          readings=out)))
    return 0


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(HERE, ".jax_cache")
    sys.path[:0] = [HERE, ROOT]
    sys.exit(main(sys.argv[1:]))
