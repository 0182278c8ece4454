"""Adapter of the two-phase commit configurations: the program's model,
and its states and actions in the plain reference's form
(``reference/twopc.py``)."""


def model(params):
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    return TwoPhaseSys(rm_count=params["rm_count"])


def plain_state(s):
    return (tuple(x.value for x in s.rm_state), s.tm_state.value,
            tuple(bool(x) for x in s.tm_prepared), tuple(sorted(s.msgs)))


def plain_action(a):
    return tuple(a)
