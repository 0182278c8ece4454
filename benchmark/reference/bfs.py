"""Plain breadth-first exploration over a reference model module.

A model module gives ``init_states(params)``, ``successors(params,
state) -> [(action, next_state)]`` (pruned transitions left out) and
``PROPERTIES = {name: ("always" | "sometimes", predicate)}``. States are
nested tuples of ints, strings and bools, whose ``repr`` is the same
for equal states; visited states are kept as 128-bit digests of it.

Semantics follow upstream stateright's breadth-first checker: a
"sometimes" property is discovered by the first state that satisfies
it, an "always" property by the first that violates it, and the search
stops once every property has a discovery (checked after each level, as
the wave engines do).
"""

import hashlib


def _key(state):
    return hashlib.blake2b(repr(state).encode(), digest_size=16).digest()


def _check(props, params, state, depth, found):
    for name, (kind, pred) in props.items():
        if name in found:
            continue
        if pred(params, state) == (kind == "sometimes"):
            found[name] = depth


def explore(model, params, max_levels=None):
    """Exhaustive BFS. Returns ``unique`` (states), ``depth`` (levels,
    the initial states being level 1), ``discoveries`` ({name: level of
    the first discovering state}) and ``waves``: for each expansion of a
    level, the unique states before it (``visited``), the states it found
    new (``new``), its distinct successors (``candidates``) and all its
    successors (``generated``)."""
    props = model.PROPERTIES
    seen = set()
    frontier = []
    found = {}
    for s in model.init_states(params):
        k = _key(s)
        if k not in seen:
            seen.add(k)
            frontier.append(s)
            _check(props, params, s, 1, found)
    depth = 1 if frontier else 0
    waves = []
    while frontier and len(found) < len(props):
        if max_levels is not None and depth >= max_levels:
            break
        nxt = []
        cand = set()
        generated = 0
        visited = len(seen)
        for s in frontier:
            for _a, t in model.successors(params, s):
                generated += 1
                k = _key(t)
                cand.add(k)
                if k in seen:
                    continue
                seen.add(k)
                nxt.append(t)
                _check(props, params, t, depth + 1, found)
        waves.append(dict(visited=visited, new=len(nxt),
                          candidates=len(cand), generated=generated))
        frontier = nxt
        if nxt:
            depth += 1
    return dict(unique=len(seen), depth=depth, discoveries=found,
                waves=waves)


def replay(model, params, prop, steps):
    """Check one counterexample or example path the program produced.

    ``steps`` is ``[(state, action_into_next), ...]`` in the reference's
    plain form, the last action ``None``. The path must start at an
    initial state, each state must follow from the one before by its
    action, and its last state must discover ``prop``. Returns a list of
    faults, empty when the path is sound."""
    faults = []
    if not steps:
        return [f"{prop}: empty path"]
    if steps[0][0] not in model.init_states(params):
        faults.append(f"{prop}: path starts at a non-initial state")
    for i in range(len(steps) - 1):
        state, action = steps[i]
        succ = dict(model.successors(params, state))
        if action not in succ:
            faults.append(f"{prop}: step {i} action {action!r} not enabled")
            break
        if succ[action] != steps[i + 1][0]:
            faults.append(f"{prop}: step {i} {action!r} leads elsewhere")
            break
    kind, pred = model.PROPERTIES[prop]
    if pred(params, steps[-1][0]) != (kind == "sometimes"):
        faults.append(f"{prop}: last state does not discover it")
    return faults
