"""Reduction soundness analyzer gate (``soundness`` marker).

The tentpole contract (analysis/soundness.py): a declared
``DeviceRewriteSpec`` or ample mask is CERTIFIED by static analysis
— no state-space enumeration — before any engine trusts it. The gate
pins:

* the two shipping specs certify — 2pc (symmetry + ample mask, all
  seven obligations) and the N-client register family (symmetry
  only), both with ZERO over-approximated primitives (the bit-level
  abstract interpreter walks their jaxprs exactly);
* the certificate's claims are TRUE on the register family — host
  DFS, host DFS + symmetry, and the device sort-merge engine under
  ``--symmetry`` agree with the closed-form counts (raw
  ``1 + 2n*3^(n-1)``, orbits ``1 + n(n+1)``), and the 2pc device
  counts match the round-20 pinned values;
* three deliberately BROKEN specs refuse with three DISTINCT
  obligations — a non-closed rewrite set (overlapping member fields)
  fails ``group-closure``, a property reading one permuted field
  asymmetrically fails ``property-invariance``, an ample mask
  dropping every member's property-relevant slot fails
  ``ample-non-suppression`` — and the refusal surfaces through the
  REAL engine spawn, not just the analyzer API;
* ``--unsound-ok`` (``CheckerBuilder.unsound_ok()``) waives the gate
  without certifying anything;
* both refusal families — the round-20 capability refusal and the
  certificate refusal — speak through one formatter
  (checkers/common.reduction_refusal);
* the walker the analyzer rides handles ``lax.cond``/``lax.switch``
  branch sub-jaxprs and closed-over constants (satellite edge
  cases);
* a certificate-status flip between two traces of one workload is a
  trace-diff DIVERGENCE (tools/trace_diff.py), and the
  ``SOUND_r*.json`` artifact round-trips through
  ``artifacts.latest_soundness_summary``.
"""

import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from stateright_tpu import telemetry  # noqa: E402
from stateright_tpu.analysis.soundness import (  # noqa: E402
    apply_member_permutation,
    analyze_main,
    certify_encoding,
    gate_ample,
    gate_symmetry,
    soundness_status,
    write_soundness_artifact,
)
from stateright_tpu.analysis.walker import (  # noqa: E402
    SiteWalk,
    iter_eqns,
    source_of,
)
from stateright_tpu.artifacts import latest_soundness_summary  # noqa: E402
from stateright_tpu.checkers.common import (  # noqa: E402
    soundness_refusal,
    symmetry_refusal,
)
from stateright_tpu.models.nclient_register import (  # noqa: E402
    NClientRegSys,
)
from stateright_tpu.models.nclient_register_tpu import (  # noqa: E402
    NClientRegEncoded,
)
from stateright_tpu.models.two_phase_commit import (  # noqa: E402
    TwoPhaseSys,
)
from stateright_tpu.models.two_phase_commit_tpu import (  # noqa: E402
    TwoPhaseSysEncoded,
)
from stateright_tpu.ops.bitmask import pack_bits_host  # noqa: E402
from stateright_tpu.ops.canonical import (  # noqa: E402
    DeviceRewriteSpec,
    MemberField,
)
from stateright_tpu.telemetry import (  # noqa: E402
    RunTracer,
    diff_traces,
    load_trace,
)

pytestmark = pytest.mark.soundness

SYM_OBLIGATIONS = (
    "group-closure",
    "orbit-structure",
    "fingerprint-invariance",
    "property-invariance",
    "transition-equivariance",
)
AMPLE_OBLIGATIONS = ("ample-enabledness", "ample-non-suppression")


# -- the three deliberately broken specs (ISSUE 18 satellite 1) ------------


class Overlap2pc(TwoPhaseSysEncoded):
    """Non-closed rewrite set: two lane-0 member fields whose bit
    ranges OVERLAP (member m's width-2 field at bit 2m and width-1
    field at bit 2m+1 share a bit), so applying two permutations in
    sequence is not the composed permutation — rebuild ORs clobbered
    bits. Structurally valid (each field alone fits its stride);
    only the semantic group-closure check can see it."""

    def device_rewrite_spec(self):
        return DeviceRewriteSpec(
            n_members=self.rm_count,
            fields=(
                MemberField(lane=0, shift=0, stride=2, width=2,
                            sort_key=True),
                MemberField(lane=0, shift=1, stride=2, width=1,
                            sort_key=True),
            ),
        )


class AsymProp(NClientRegEncoded):
    """Property reading a permuted field ASYMMETRICALLY: the extra
    condition looks only at client 0's 4-bit block, so permuting
    clients flips the property verdict between orbit members."""

    def property_conditions_vec(self, vec):
        base = super().property_conditions_vec(vec)
        return base.at[0].set((vec[1] & jnp.uint32(3)) == 2)


class BadAmple(TwoPhaseSysEncoded):
    """Ample mask suppressing an enabled property-relevant action:
    drop slot ``4 + 5*rm`` for EVERY member, so the dropped
    transitions have no symmetric kept image — the reduced graph can
    miss property-relevant successors."""

    def ample_mask_host(self):
        keep = np.ones(self.max_actions, dtype=bool)
        for rm in range(self.rm_count):
            keep[4 + 5 * rm] = False
        return pack_bits_host(keep)


def _failed_rules(res):
    return [f.rule for f in res.obligations if f.severity == "error"]


# -- the shipping specs certify --------------------------------------------


def test_2pc_certifies_all_seven_obligations():
    res = certify_encoding(TwoPhaseSysEncoded(4), use_cache=False)
    # fully precise interpretation: nothing was over-approximated (a
    # call primitive the analyzer does not enter lands here first)
    assert res.collapsed == []
    assert res.certified
    assert res.sym_certified is True
    assert res.ample_certified is True
    rules = [f.rule for f in res.obligations]
    assert tuple(rules) == SYM_OBLIGATIONS + AMPLE_OBLIGATIONS
    assert all(f.severity == "info" for f in res.obligations)
    assert res.analyzer_sec > 0


def test_call_primitive_names_match_jax():
    """Both jaxpr walkers enter the call primitive this JAX emits for
    a nested jit: a rename (``pjit`` became ``jit``) fails here instead
    of silently over-approximating every nested call."""
    import jax

    from stateright_tpu.analysis import soundness, walker

    jx = jax.make_jaxpr(lambda x: jax.jit(lambda y: y + 1)(x))(1)
    name = jx.jaxpr.eqns[0].primitive.name
    assert name in soundness._CALL_PRIMS
    assert name in walker._CALL_PRIMS


def test_register_family_certifies_symmetry():
    res = certify_encoding(NClientRegEncoded(4), use_cache=False)
    assert res.certified
    assert res.sym_certified is True
    assert res.ample_certified is None  # no mask declared
    assert tuple(f.rule for f in res.obligations) == SYM_OBLIGATIONS
    assert res.collapsed == []


def test_soundness_status_views():
    assert soundness_status(NClientRegEncoded(3)) is True
    assert soundness_status(Overlap2pc(3)) is False

    class NoReductions:
        width, max_actions = 1, 1

    assert soundness_status(NoReductions()) is None


def test_apply_member_permutation_matches_encode():
    """The analyzer's group action agrees with the encoding: permuting
    members of an encoded row equals encoding the permuted state."""
    enc = NClientRegEncoded(3)
    spec = enc.device_rewrite_spec()
    model = NClientRegSys(3)
    s = model.init_states()[0]
    for s2 in model.next_states(s):
        s = s2  # a non-trivial state (one client wrote)
        break
    row = enc.encode(s)
    perm = (2, 0, 1)  # output member p takes input member perm[p]
    got = apply_member_permutation(spec, row[None, :], perm)[0]
    from dataclasses import replace

    want = enc.encode(
        replace(s, clients=tuple(s.clients[p] for p in perm))
    )
    assert np.array_equal(got, want)


# -- the certificate's claims are true (pinned counts) ---------------------


def test_register_counts_host_and_device():
    """Closed-form counts, three ways: raw host DFS, host DFS +
    symmetry, device sort-merge + symmetry (n=4: raw 217, orbits 21)."""
    n = 4
    raw = 1 + 2 * n * 3 ** (n - 1)
    orbits = 1 + n * (n + 1)
    assert (raw, orbits) == (217, 21)

    host_raw = NClientRegSys(n).checker().spawn_dfs().join()
    assert host_raw.unique_state_count() == raw

    host_sym = (
        NClientRegSys(n).checker().symmetry().spawn_dfs().join()
    )
    assert host_sym.unique_state_count() == orbits

    dev_sym = (
        NClientRegSys(n)
        .checker()
        .symmetry()
        .spawn_tpu_sortmerge(
            capacity=1 << 10, frontier_capacity=128,
            cand_capacity=512, waves_per_sync=2,
        )
        .join()
    )
    assert dev_sym.unique_state_count() == orbits


def test_2pc_device_symmetry_count_unchanged():
    """The certificate gate must not perturb the round-20 pinned
    reduction: 2pc rm=3 under --symmetry still visits exactly 80."""
    c = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .symmetry()
        .spawn_tpu_sortmerge(
            capacity=1 << 10, frontier_capacity=128,
            cand_capacity=512, waves_per_sync=2,
        )
        .join()
    )
    assert c.unique_state_count() == 80


# -- the three broken specs refuse, distinctly -----------------------------


def test_overlap_spec_fails_group_closure():
    res = certify_encoding(Overlap2pc(3), use_cache=False)
    assert not res.certified
    assert res.sym_certified is False
    assert res.failed("symmetry").rule == "group-closure"
    # group-closure failing short-circuits the other symmetry checks
    sym_rules = [f.rule for f in res.obligations
                 if f.data.get("scope") == "symmetry"]
    assert sym_rules == ["group-closure"]
    # collateral: the inherited 2pc ample mask loses its symmetric-
    # image argument once the spec is uncertified — also refused
    assert res.ample_certified is False


def test_asym_property_fails_property_invariance():
    res = certify_encoding(AsymProp(4), use_cache=False)
    assert not res.certified
    assert res.sym_certified is False
    assert _failed_rules(res) == ["property-invariance"]


def test_bad_ample_fails_non_suppression_only():
    """The guards of the dropped slots are member-symmetric (every
    member's slot is dropped), so ample-enabledness PASSES — the mask
    fails precisely on non-suppression: an enabled property-relevant
    transition has no symmetric kept image."""
    res = certify_encoding(BadAmple(3), use_cache=False)
    assert not res.certified
    assert res.sym_certified is True  # the spec itself is fine
    assert res.ample_certified is False
    assert _failed_rules(res) == ["ample-non-suppression"]


def test_refusals_are_distinct_and_name_the_obligation():
    msgs = {}
    for enc, scope in (
        (Overlap2pc(3), "symmetry"),
        (AsymProp(4), "symmetry"),
        (BadAmple(3), "ample"),
    ):
        res = certify_encoding(enc)
        f = res.failed(scope)
        msgs[f.rule] = f.message
    assert set(msgs) == {
        "group-closure", "property-invariance",
        "ample-non-suppression",
    }
    assert len(set(msgs.values())) == 3


def test_engine_refuses_overlap_spec_at_spawn():
    with pytest.raises(ValueError, match="group-closure"):
        (
            TwoPhaseSys(rm_count=3)
            .checker()
            .symmetry()
            .spawn_tpu_sortmerge(
                encoded=Overlap2pc(3), capacity=1 << 10,
                frontier_capacity=128, cand_capacity=512,
            )
        )


def test_engine_refuses_asym_property_at_spawn():
    with pytest.raises(ValueError, match="property-invariance"):
        (
            NClientRegSys(4)
            .checker()
            .symmetry()
            .spawn_tpu_sortmerge(
                encoded=AsymProp(4), capacity=1 << 10,
                frontier_capacity=128, cand_capacity=512,
            )
        )


def test_engine_refuses_bad_ample_at_program_build():
    c = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .spawn_tpu_sortmerge(
            encoded=BadAmple(3), capacity=1 << 10,
            frontier_capacity=128, cand_capacity=2048,
            ample_set=True,
        )
    )
    with pytest.raises(ValueError, match="ample-non-suppression"):
        c.join()


# -- the --unsound-ok escape hatch -----------------------------------------


def test_unsound_ok_waives_both_gates():
    assert gate_symmetry(Overlap2pc(3), "spawn_x",
                         unsound_ok=True) is False
    assert gate_ample(BadAmple(3), "spawn_x",
                      unsound_ok=True) is False
    # certified specs gate True regardless
    assert gate_symmetry(NClientRegEncoded(4), "spawn_x") is True


def test_unsound_ok_builder_spawns_uncertified_spec():
    """``CheckerBuilder.unsound_ok()`` reaches the spawn gate: the
    overlap spec that refuses above constructs without raising."""
    c = (
        TwoPhaseSys(rm_count=3)
        .checker()
        .unsound_ok()
        .symmetry()
        .spawn_tpu_sortmerge(
            encoded=Overlap2pc(3), capacity=1 << 10,
            frontier_capacity=128, cand_capacity=512,
        )
    )
    assert c.unsound_ok is True
    assert c.sym_spec is not None


# -- one refusal formatter (satellite 2) -----------------------------------


def test_refusal_families_share_the_formatter():
    head = "symmetry reduction: spawn_x cannot honor it"
    cap = str(symmetry_refusal("spawn_x", missing="a spec"))
    cert = str(soundness_refusal(
        "spawn_x", "symmetry", "group-closure", "not a group"
    ))
    assert cap.startswith(head)
    assert cert.startswith(head)
    assert "missing capability" in cap
    assert "obligation 'group-closure' failed" in cert
    assert "--unsound-ok" in cert
    amp = str(soundness_refusal(
        "spawn_x", "ample-set", "ample-enabledness", "d"
    ))
    assert amp.startswith(
        "ample-set reduction: spawn_x cannot honor it"
    )


# -- walker edge cases (satellite 3) ---------------------------------------


def test_walker_enters_cond_branches():
    def f(x):
        return jax.lax.cond(
            x[0] > 0, lambda v: v + 1, lambda v: v * 2, x
        )

    closed = jax.make_jaxpr(f)(np.zeros(2, np.int32))
    walk = SiteWalk(closed)
    assert any(s.primitive == "cond" for s in walk)
    sub = [s for s in walk
           if s.stack and s.stack[-1][0] == "cond"]
    # both branch bodies walked, branch index recorded on the stack
    assert {s.stack[-1][1] for s in sub} == {0, 1}
    assert any(s.in_branch() for s in walk)
    for s in sub:
        assert isinstance(source_of(s.eqn), str)


def test_walker_enters_all_switch_branches():
    def f(x):
        branches = [
            lambda v: v + 1,
            lambda v: v * 2,
            lambda v: v - 3,
        ]
        return jax.lax.switch(x[0], branches, x)

    closed = jax.make_jaxpr(f)(np.zeros(2, np.int32))
    sites = list(iter_eqns(closed.jaxpr))
    sub = [s for s in sites
           if s.stack and s.stack[-1][0] == "cond"]
    assert {s.stack[-1][1] for s in sub} == {0, 1, 2}
    # each branch sub-jaxpr is distinct and owns its equations
    assert len({id(s.jaxpr) for s in sub}) == 3


def test_walker_closed_over_constants():
    table = np.arange(1, 5, dtype=np.int32)

    def f(x):
        return x * jnp.asarray(table)

    closed = jax.make_jaxpr(f)(np.zeros(4, np.int32))
    assert len(closed.consts) == 1
    assert np.array_equal(np.asarray(closed.consts[0]), table)
    # constvars are real Vars (the analyzer keys env by id, and the
    # literal test is the absence of .count)
    assert all(hasattr(v, "count") for v in closed.jaxpr.constvars)
    walk = SiteWalk(closed)
    assert any(s.primitive == "mul" for s in walk)


def test_analyzer_interprets_cond_exactly():
    """An encoding-shaped fn with a data-dependent ``lax.cond`` still
    interprets without collapse when both branches are bit-tractable:
    certify the register spec against a property that routes through
    cond (the interpreter joins the branches with the pred's deps)."""

    class CondProp(NClientRegEncoded):
        def property_conditions_vec(self, vec):
            base = super().property_conditions_vec(vec)
            # pred reads the (unpermuted) register lane; both branch
            # values are whole-lane facts, invariant under permuting
            # the client blocks
            extra = jax.lax.cond(
                (vec[0] & jnp.uint32(1)) != 0,
                lambda v: (v[0] | v[1]) != jnp.uint32(0),
                lambda v: v[1] == v[1],
                vec,
            )
            return jnp.concatenate([base, extra[None]])

    res = certify_encoding(CondProp(3), use_cache=False)
    # both branches are symmetric in the clients, so it certifies
    assert res.sym_certified is True


# -- certificate flip is a trace divergence (satellite 5) ------------------


def _cert_trace(tmp_path, name, certified):
    tr = RunTracer()
    with tr.activate():
        tr.begin_run(lane=dict(
            engine="T", soundness_certified=certified,
        ))
        with telemetry.span("compile"):
            pass
        tr.record_chunk(
            chunk=0, wave0=0, t0=0.0, t1=1.0,
            dispatch_sec=0.01, fetch_sec=0.5,
            wave_rows=np.array([[4, 6, 5, 4, 5, 1, 0, 0]]),
        )
        tr.end_run(error=None, total_states=4, unique_states=5,
                   max_depth=1, duration_sec=1.0)
    path = str(tmp_path / name)
    tr.write_jsonl(path)
    return load_trace(path)


def test_cert_status_flip_diffs_as_divergence(tmp_path):
    a = _cert_trace(tmp_path, "a.jsonl", True)
    b = _cert_trace(tmp_path, "b.jsonl", False)
    same = diff_traces(a, _cert_trace(tmp_path, "a2.jsonl", True))
    assert same["ok"]
    rep = diff_traces(a, b)
    assert not rep["ok"]
    flips = [d for d in rep["divergences"]
             if d["field"] == "soundness_certified"]
    assert flips and flips[0]["a"] is True and flips[0]["b"] is False


# -- artifact + CLI (satellites 4/5) ---------------------------------------


def test_sound_artifact_roundtrip(tmp_path):
    root = str(tmp_path)
    res = certify_encoding(NClientRegEncoded(4))
    path = write_soundness_artifact([res], root=root)
    assert os.path.basename(path) == "SOUND_r01.json"
    with open(path) as fh:
        report = json.load(fh)
    assert report["schema"] == "soundness-cert/v1"
    assert report["clean"] is True
    (spec_dict,) = report["specs"].values()
    assert spec_dict["status"] == "certified"
    assert spec_dict["collapsed_primitives"] == []

    summary = latest_soundness_summary(root)
    assert summary is not None
    assert summary["clean"] is True
    assert set(summary["specs"].values()) == {"certified"}

    # own round sequence: the next write is r02
    path2 = write_soundness_artifact([res], root=root)
    assert os.path.basename(path2) == "SOUND_r02.json"
    assert os.path.basename(
        latest_soundness_summary(root)["artifact"]
    ) == "SOUND_r02.json"


def test_refused_spec_marks_artifact_dirty(tmp_path):
    root = str(tmp_path)
    res = certify_encoding(Overlap2pc(3))
    write_soundness_artifact([res], root=root)
    summary = latest_soundness_summary(root)
    assert summary["clean"] is False
    assert set(summary["specs"].values()) == {"refused"}


def test_analyze_cli_smoke(capsys, monkeypatch, tmp_path):
    monkeypatch.chdir(tmp_path)  # keep any artifact out of the repo
    assert analyze_main(
        ["soundness", "register", "3", "--no-artifact"]
    ) == 0
    out = capsys.readouterr().out
    assert "certified" in out
    assert "ok  group-closure" in out

    assert analyze_main(["soundness", "no-such-model"]) == 2
    assert analyze_main([]) == 2


def test_committed_certificate_is_current():
    """The repo-root SOUND artifact (satellite 5) exists, is clean,
    and certifies both shipping targets."""
    summary = latest_soundness_summary()
    assert summary is not None, "no SOUND_r*.json committed"
    assert summary["clean"] is True
    names = " ".join(summary["specs"])
    assert "TwoPhaseSysEncoded" in names
    assert "NClientRegEncoded" in names
