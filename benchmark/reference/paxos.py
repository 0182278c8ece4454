"""Plain reference of single-decree Paxos serving a register (upstream
stateright ``examples/paxos.rs``: ``paxos check N``), written from the
protocol and independent of the checker under test: it imports nothing
of ``stateright_tpu``.

The system is ``server_count`` Paxos servers (ids ``0..S-1``) and
``client_count`` register clients (ids ``S..``), over an unordered
network that neither loses nor duplicates messages, with the history
of client operations kept in the state for the linearizability check.

A state is ``(servers, clients, network, history)``, all plain tuples:

* server: ``(ballot, proposal, prepares, accepts, accepted, decided)``,
  a ballot being ``(round, leader)``, a proposal ``(req_id, client,
  value)``, ``prepares`` a sorted tuple of ``(server, last_accepted)``,
  ``accepts`` a sorted tuple of servers, ``accepted`` ``(ballot,
  proposal)``; an absent value is ``()``;
* client: ``(awaiting, op_count)``, ``awaiting`` ``()`` once done;
* network: a sorted tuple of ``((src, dst, msg), count)``;
* history: ``(completed, in_flight, valid)``: ``completed`` a sorted
  tuple of ``(client, ((snapshot, op, ret), ...))``, ``in_flight`` a
  sorted tuple of ``(client, (snapshot, op))``, where ``snapshot`` is
  the sorted ``(other client, index of its last completed op)`` at the
  time of the invocation (upstream ``LinearizabilityTester``).

An action is the envelope ``(src, dst, msg)`` it delivers.
"""

NONE = ()
DEFAULT_VALUE = "\x00"


def _majority(n):
    return n // 2 + 1


# -- network and history -------------------------------------------------


def _net_send(net, env):
    d = dict(net)
    d[env] = d.get(env, 0) + 1
    return tuple(sorted(d.items()))


def _net_deliver(net, env):
    d = dict(net)
    if d[env] == 1:
        del d[env]
    else:
        d[env] -= 1
    return tuple(sorted(d.items()))


def _invoke(hist, thread, op):
    completed, in_flight, valid = hist
    if not valid:
        return hist
    fl = dict(in_flight)
    if thread in fl:
        return (completed, in_flight, False)
    done = dict(completed)
    snapshot = tuple(sorted((peer, len(ops) - 1)
                            for peer, ops in done.items()
                            if peer != thread and ops))
    fl[thread] = (snapshot, op)
    done.setdefault(thread, ())
    return (tuple(sorted(done.items())), tuple(sorted(fl.items())), True)


def _return(hist, thread, ret):
    completed, in_flight, valid = hist
    if not valid:
        return hist
    fl = dict(in_flight)
    if thread not in fl:
        return (completed, in_flight, False)
    snapshot, op = fl.pop(thread)
    done = dict(completed)
    done[thread] = done.get(thread, ()) + ((snapshot, op, ret),)
    return (tuple(sorted(done.items())), tuple(sorted(fl.items())), True)


def _record_out(hist, env):
    src, _dst, msg = env
    if msg[0] == "Put":
        return _invoke(hist, src, ("write", msg[2]))
    if msg[0] == "Get":
        return _invoke(hist, src, ("read",))
    return hist


def _record_in(hist, env):
    _src, dst, msg = env
    if msg[0] == "GetOk":
        return _return(hist, dst, ("read_ok", msg[2]))
    if msg[0] == "PutOk":
        return _return(hist, dst, ("write_ok",))
    return hist


# -- linearizability of a register history --------------------------------

_LIN_MEMO = {}


def linearizable(hist):
    """Whether some total order of the history, consistent with each
    client's program order and with real time, is a legal run of a
    register starting at ``DEFAULT_VALUE``. In-flight operations may
    take effect or not."""
    completed, in_flight, valid = hist
    if not valid:
        return False
    if hist in _LIN_MEMO:
        return _LIN_MEMO[hist]
    done = dict(completed)
    fl = dict(in_flight)
    threads = sorted(set(done) | set(fl))
    total = {t: len(done.get(t, ())) for t in threads}
    failed = set()

    def blocked(snapshot, pos):
        return any(pos.get(peer, 0) <= last for peer, last in snapshot)

    def rec(pos, consumed, value):
        if all(pos[t] == total[t] for t in threads):
            return True
        key = (tuple(pos[t] for t in threads), consumed, value)
        if key in failed:
            return False
        for t in threads:
            if pos[t] < total[t]:
                snapshot, op, ret = done[t][pos[t]]
                if blocked(snapshot, pos):
                    continue
                if op[0] == "write":
                    nv = op[1]
                elif ret[1] == value:
                    nv = value
                else:
                    continue
                if rec({**pos, t: pos[t] + 1}, consumed, nv):
                    return True
            elif t in fl and t not in consumed:
                snapshot, op = fl[t]
                if blocked(snapshot, pos):
                    continue
                nv = op[1] if op[0] == "write" else value
                if rec(pos, consumed | {t}, nv):
                    return True
        failed.add(key)
        return False

    ok = rec({t: 0 for t in threads}, frozenset(), DEFAULT_VALUE)
    _LIN_MEMO[hist] = ok
    return ok


# -- actors ---------------------------------------------------------------


def _server_on_msg(me, n_servers, state, src, msg):
    """``(new_state or None, [(dst, msg), ...])``; ``None`` with no
    sends means the delivery changes nothing and is pruned."""
    ballot, proposal, prepares, accepts, accepted, decided = state
    peers = [j for j in range(n_servers) if j != me]
    kind = msg[0]
    if decided:
        if kind == "Get":
            _b, (_req, _client, value) = accepted
            return None, [(src, ("GetOk", msg[1], value))]
        return None, []
    if kind == "Put" and proposal == NONE:
        b = (ballot[0] + 1, me)
        new = (b, (msg[1], src, msg[2]), ((me, accepted),), (), accepted,
               False)
        return new, [(p, ("Prepare", b)) for p in peers]
    if kind == "Prepare":
        if ballot < msg[1]:
            return ((msg[1], proposal, prepares, accepts, accepted, False),
                    [(src, ("Prepared", msg[1], accepted))])
        return None, []
    if kind == "Prepared":
        if msg[1] != ballot:
            return None, []
        d = dict(prepares)
        d[src] = msg[2]
        prepares = tuple(sorted(d.items()))
        if len(prepares) == _majority(n_servers):
            best = max(last for _s, last in prepares)
            prop = best[1] if best != NONE else proposal
            return ((ballot, prop, prepares, (me,), (ballot, prop), False),
                    [(p, ("Accept", ballot, prop)) for p in peers])
        return (ballot, proposal, prepares, accepts, accepted, False), []
    if kind == "Accept":
        if ballot <= msg[1]:
            return ((msg[1], proposal, prepares, accepts,
                     (msg[1], msg[2]), False),
                    [(src, ("Accepted", msg[1]))])
        return None, []
    if kind == "Accepted":
        if msg[1] != ballot:
            return None, []
        accepts = tuple(sorted(set(accepts) | {src}))
        if len(accepts) == _majority(n_servers):
            req_id, requester, _value = proposal
            sends = [(p, ("Decided", ballot, proposal)) for p in peers]
            sends.append((requester, ("PutOk", req_id)))
            return ((ballot, proposal, prepares, accepts, accepted, True),
                    sends)
        return (ballot, proposal, prepares, accepts, accepted, False), []
    if kind == "Decided":
        return ((msg[1], proposal, prepares, accepts, (msg[1], msg[2]),
                 True), [])
    return None, []


def _client_on_start(me, n_servers, put_count):
    if put_count == 0:
        return (NONE, 0), []
    value = chr(ord("A") + me - n_servers)
    return (me, 1), [(me % n_servers, ("Put", me, value))]


def _client_on_msg(me, n_servers, put_count, state, msg):
    awaiting, op_count = state
    if awaiting == NONE:
        return None, []
    if msg[0] == "PutOk" and msg[1] == awaiting:
        req_id = (op_count + 1) * me
        dst = (me + op_count) % n_servers
        if op_count < put_count:
            value = chr(ord("Z") - (me - n_servers))
            send = (dst, ("Put", req_id, value))
        else:
            send = (dst, ("Get", req_id))
        return (req_id, op_count + 1), [send]
    if msg[0] == "GetOk" and msg[1] == awaiting:
        return (NONE, op_count + 1), []
    return None, []


# -- the model --------------------------------------------------------------


def _sizes(params):
    return (params["server_count"], params["client_count"],
            params.get("put_count", 1))


def _emit(hist, net, src, sends):
    for dst, msg in sends:
        env = (src, dst, msg)
        hist = _record_out(hist, env)
        net = _net_send(net, env)
    return hist, net


def init_states(params):
    n_s, n_c, puts = _sizes(params)
    server0 = ((0, 0), NONE, (), (), NONE, False)
    hist = ((), (), True)
    net = ()
    clients = []
    for c in range(n_s, n_s + n_c):
        st, sends = _client_on_start(c, n_s, puts)
        clients.append(st)
        hist, net = _emit(hist, net, c, sends)
    return [((server0,) * n_s, tuple(clients), net, hist)]


def successors(params, state):
    n_s, _n_c, puts = _sizes(params)
    servers, clients, net, hist = state
    out = []
    for env, _count in net:
        src, dst, msg = env
        if dst < n_s:
            new, sends = _server_on_msg(dst, n_s, servers[dst], src, msg)
        else:
            new, sends = _client_on_msg(dst, n_s, puts,
                                        clients[dst - n_s], msg)
        if new is None and not sends:
            continue
        h = _record_in(hist, env)
        n = _net_deliver(net, env)
        ss, cs = servers, clients
        if new is not None:
            if dst < n_s:
                ss = ss[:dst] + (new,) + ss[dst + 1:]
            else:
                i = dst - n_s
                cs = cs[:i] + (new,) + cs[i + 1:]
        h, n = _emit(h, n, dst, sends)
        out.append((env, (ss, cs, n, h)))
    return out


def _value_chosen(params, state):
    return any(msg[0] == "GetOk" and msg[2] != DEFAULT_VALUE
               for (_src, _dst, msg), _n in state[2])


PROPERTIES = {
    "linearizable": ("always", lambda p, s: linearizable(s[3])),
    "value chosen": ("sometimes", _value_chosen),
}
