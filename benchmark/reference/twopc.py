"""Plain reference of two-phase commit (upstream stateright
``examples/2pc.rs``), written from the protocol and independent of the
checker under test: it imports nothing of ``stateright_tpu``.

A state is ``(rm_state, tm_state, tm_prepared, msgs)``: ``rm_state`` a
tuple of ints (0 working, 1 prepared, 2 committed, 3 aborted),
``tm_state`` an int (0 init, 1 committed, 2 aborted), ``tm_prepared`` a
tuple of bools and ``msgs`` a sorted tuple of ``("prepared", rm)``,
``("commit",)`` and ``("abort",)``. An action is a tuple whose first
item names it (``("rm_prepare", 3)``, ``("tm_commit",)``, ...).
"""

WORKING, PREPARED, COMMITTED, ABORTED = 0, 1, 2, 3
TM_INIT, TM_COMMITTED, TM_ABORTED = 0, 1, 2


def init_states(params):
    n = params["rm_count"]
    return [((WORKING,) * n, TM_INIT, (False,) * n, ())]


def _with(t, i, v):
    return t[:i] + (v,) + t[i + 1:]


def _send(msgs, m):
    return msgs if m in msgs else tuple(sorted(msgs + (m,)))


def actions(params, state):
    rm_state, tm_state, tm_prepared, msgs = state
    out = []
    if tm_state == TM_INIT and all(tm_prepared):
        out.append(("tm_commit",))
    if tm_state == TM_INIT:
        out.append(("tm_abort",))
    for rm in range(params["rm_count"]):
        if tm_state == TM_INIT and ("prepared", rm) in msgs:
            out.append(("tm_rcv_prepared", rm))
        if rm_state[rm] == WORKING:
            out.append(("rm_prepare", rm))
            out.append(("rm_choose_abort", rm))
        if ("commit",) in msgs:
            out.append(("rm_rcv_commit", rm))
        if ("abort",) in msgs:
            out.append(("rm_rcv_abort", rm))
    return out


def next_state(params, state, action):
    rm_state, tm_state, tm_prepared, msgs = state
    kind = action[0]
    if kind == "tm_commit":
        return (rm_state, TM_COMMITTED, tm_prepared, _send(msgs, ("commit",)))
    if kind == "tm_abort":
        return (rm_state, TM_ABORTED, tm_prepared, _send(msgs, ("abort",)))
    rm = action[1]
    if kind == "tm_rcv_prepared":
        return (rm_state, tm_state, _with(tm_prepared, rm, True), msgs)
    if kind == "rm_prepare":
        return (_with(rm_state, rm, PREPARED), tm_state, tm_prepared,
                _send(msgs, ("prepared", rm)))
    if kind in ("rm_choose_abort", "rm_rcv_abort"):
        return (_with(rm_state, rm, ABORTED), tm_state, tm_prepared, msgs)
    if kind == "rm_rcv_commit":
        return (_with(rm_state, rm, COMMITTED), tm_state, tm_prepared, msgs)
    raise ValueError(f"unknown action {action!r}")


def successors(params, state):
    return [(a, next_state(params, state, a)) for a in actions(params, state)]


PROPERTIES = {
    "abort agreement": ("sometimes",
                        lambda p, s: all(x == ABORTED for x in s[0])),
    "commit agreement": ("sometimes",
                         lambda p, s: all(x == COMMITTED for x in s[0])),
    "consistent": ("always",
                   lambda p, s: not (ABORTED in s[0] and COMMITTED in s[0])),
}
