"""The comparison that decides ``correct``: every check of the run
against the plain reference.

The reference's answer to a full exploration is recorded in the
configuration under ``expect`` by ``reference/record.py`` (a test
recomputes it for the configurations small enough for the CPU). Paths
are replayed through the reference itself after the window.

Each check is compared on:

* its unique state count and its depth (the number of BFS levels),
  against the reference's: fingerprinting, the step, candidate sort,
  membership and the visited-set merge all show in the count;
* the set of properties it discovered: the property predicates;
* where the configuration tracks paths, each discovery path, replayed
  step by step through the reference from an initial state to a state
  that discovers the property, at the reference's shortest depth.

All comparisons are exact, so every limit is 0.
"""

from reference import bfs


def _plain_steps(system, steps):
    return [(system.plain_state(s),
             None if a is None else system.plain_action(a))
            for s, a in steps]


def judge(cfg, checks, ref_mod, system):
    """``correct``, ``failed`` (checks that raised or answered
    differently), ``ok`` (per check), ``compared`` (each number with its
    limit) and the ``reference`` answer."""
    ref = cfg["expect"]
    want_props = sorted(ref["discoveries"])
    track = cfg["engine"].get("track_paths", True)
    replayed = {}
    unique_gap = depth_gap = props_bad = paths_bad = errors = 0
    ok = []
    for chk in checks:
        if chk["error"] is not None:
            errors += 1
            ok.append(False)
            continue
        u = abs(chk["unique"] - ref["unique"])
        d = abs(chk["depth"] - ref["depth"])
        p = chk["discoveries"] != want_props
        bad = 0
        if track:
            for name in want_props:
                steps = chk.get("paths", {}).get(name)
                if steps is None:
                    bad += 1
                    continue
                plain = _plain_steps(system, steps)
                key = repr(plain)
                if key not in replayed:
                    faults = bfs.replay(ref_mod, cfg["params"], name, plain)
                    if len(plain) != ref["discoveries"][name]:
                        faults.append(
                            f"{name}: path of {len(plain)} states, the "
                            f"shortest has {ref['discoveries'][name]}")
                    replayed[key] = faults
                bad += bool(replayed[key])
        unique_gap = max(unique_gap, u)
        depth_gap = max(depth_gap, d)
        props_bad += p
        paths_bad += bad
        ok.append(u == 0 and d == 0 and not p and bad == 0)
    compared = {
        "checks_failed": errors,
        "unique_gap": unique_gap,
        "depth_gap": depth_gap,
        "property_set_mismatches": props_bad,
    }
    if track:
        compared["bad_paths"] = paths_bad
    compared = {k: {"value": v, "limit": 0} for k, v in compared.items()}
    failed = sum(not x for x in ok)
    return dict(
        correct=bool(checks) and failed == 0,
        failed=failed, ok=ok, compared=compared, reference=ref,
    )
