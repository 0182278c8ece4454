#!/usr/bin/env python
"""Comms-lint CLI: pin the mesh communication contract, on CPU.

Runs the comms rule family (stateright_tpu/analysis/comms.py —
``no-collective-in-switch``, ``no-unsorted-all-to-all``,
``scalar-only-reductions``, ``no-all-gather``, the gated
``comms-bytes`` budget) over the mesh engine's full wave body
(traced, untraced and tiered forms, real S=2 mesh), the
rm=5/S=8 reconciliation fixture at the committed TRACE_r16 dryrun
config, and every registry encoding's ``engine:sharded`` pair
pipeline. Exit status 0 iff clean — the same gate ``pytest -m lint``
runs in tier-1 (tests/test_comms_lint.py).

Usage:
  python tools/lint_comms.py                 # human report, exit != 0 on findings
  python tools/lint_comms.py --json          # also write COMM_r*.json
  python tools/lint_comms.py --json out.json
  python tools/lint_comms.py --no-wave-body  # registry encodings only
  python tools/lint_comms.py --hlo           # compile each wave body and
                                             # reconcile the module's
                                             # collective ops vs the jaxpr
                                             # estimate (slower)

``--json`` artifacts number in their OWN ``COMM_r*`` sequence (like
MEM; stateright_tpu/artifacts.py): a COMM artifact is the static
communication contract at one commit — bench.py and lint_kernels.py
cross-reference the newest one by name (artifacts.latest_comms_summary)
instead of sharing the BENCH/LINT round counter.
"""

import argparse
import json
import os
import sys

# The reconciliation fixture needs an 8-device mesh; claim the virtual
# CPU devices BEFORE jax initializes a backend (no-op when the caller
# already set a count).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main():
    ap = argparse.ArgumentParser(
        description="static comms-lint over the sharded wave paths"
    )
    ap.add_argument(
        "--json", nargs="?", const="auto", default=None,
        metavar="PATH",
        help="write the report as JSON (default: auto-numbered "
        "COMM_r*.json in the repo root)",
    )
    ap.add_argument(
        "--no-wave-body", action="store_true",
        help="skip the engine wave-body fixtures (registry encodings "
        "only)",
    )
    ap.add_argument(
        "--no-reconciliation", action="store_true",
        help="skip the rm=5/S=8 TRACE_r16-config fixture",
    )
    ap.add_argument(
        "--hlo", action="store_true",
        help="also compile each wave-body fixture and reconcile the "
        "optimized module's collective ops against the jaxpr "
        "estimate (slower: compiles the full wave bodies)",
    )
    args = ap.parse_args()
    if args.hlo and args.no_wave_body:
        # the HLO cross-check compiles the wave-body fixtures; with
        # them skipped there is nothing to reconcile — exiting 0 as
        # if the check ran would be a silent pass
        ap.error("--hlo requires the wave-body fixtures "
                 "(drop --no-wave-body)")

    import jax

    jax.config.update("jax_platforms", "cpu")

    from stateright_tpu.analysis.comms import (
        format_comms_report,
        hlo_collective_crosscheck,
        run_comms_lint,
    )

    # the gate traces each wave-body fixture once; --hlo reuses the
    # same fixture objects (fn + carry shapes) instead of rebuilding
    # the mesh engines and re-tracing
    fixtures: list = []
    report = run_comms_lint(
        wave_bodies=not args.no_wave_body,
        reconciliation=not args.no_reconciliation,
        fixtures_out=fixtures,
    )

    if args.hlo and not args.no_wave_body:
        hlo_block = {}
        for fixture in fixtures:
            jaxpr_cats = report["comms"][fixture["name"]].get(
                "per_category", {}
            )
            xc = hlo_collective_crosscheck(fixture, jaxpr_cats)
            hlo_block[fixture["name"]] = dict(
                hlo=xc["hlo"],
                jaxpr=xc["jaxpr"],
                byte_ratio=xc["byte_ratio"],
            )
            report["findings"].extend(
                f.as_dict() for f in xc["findings"]
            )
            if any(f.severity == "error" for f in xc["findings"]):
                report["clean"] = False
        report["hlo"] = hlo_block

    print(format_comms_report(report))
    if args.hlo and "hlo" in report:
        print("hlo collective reconciliation (ops jaxpr->hlo, "
              "byte ratio):")
        for name, h in report["hlo"].items():
            for cat in sorted(set(h["jaxpr"]) | set(h["hlo"])):
                j = h["jaxpr"].get(cat, {"eqns": 0})
                c = h["hlo"].get(cat, {"ops": 0})
                r = h["byte_ratio"].get(cat)
                print(f"  {name:44s} {cat:12s} "
                      f"{j['eqns']:3d} -> {c['ops']:3d}"
                      + (f"  x{r}" if r is not None else ""))

    if args.json is not None:
        from stateright_tpu.artifacts import (
            artifact_path,
            next_round,
            provenance,
            repo_root,
        )

        report["provenance"] = provenance(
            lane=dict(
                wave_bodies=not args.no_wave_body,
                reconciliation=not args.no_reconciliation,
                hlo=args.hlo,
            )
        )
        if args.json == "auto":
            root = repo_root()
            path = artifact_path(
                "COMM", "json", root=root,
                round=next_round(root, stems=("COMM",)),
            )
        else:
            path = args.json
        with open(path, "w") as fh:
            json.dump(report, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"wrote {path}")

    sys.exit(0 if report["clean"] else 1)


if __name__ == "__main__":
    main()
