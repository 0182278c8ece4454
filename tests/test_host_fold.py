"""The host fold of one row against the limb fold it stands in for.

``fingerprint_row_host`` folds one encoded row in Python ints; every
host path replay (``TpuBfsChecker._vec_fp``) fingerprints successors
with it and must match, bit for bit, the keys the device engines wrote
with ``fingerprint_u32v``. These tests pin the two folds together at
every width the bundled encodings use, on the reserved-zero branch,
under the sort-merge engine's all-ones clamp, and end to end on a 2pc
check whose discovery paths are replayed under a tracer.
"""

import numpy as np
import pytest

from stateright_tpu.analysis.registry import ENCODINGS
from stateright_tpu.ops import fingerprint as fpmod
from stateright_tpu.ops.fingerprint import (
    fingerprint_row_host,
    fingerprint_u32v,
)

MASK64 = (1 << 64) - 1


def _limb_fp(row) -> int:
    lo, hi = fingerprint_u32v(np.asarray(row, np.uint32)[None], np)
    return (int(hi[0]) << 32) | int(lo[0])


def _rows(width: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    rand = rng.integers(0, 1 << 32, size=(64, width), dtype=np.uint64)
    small = rng.integers(0, 8, size=(32, width), dtype=np.uint64)
    edges = np.array(
        [[0] * width, [0xFFFFFFFF] * width, [1] + [0] * (width - 1)],
        dtype=np.uint64,
    )
    return np.concatenate([rand, small, edges]).astype(np.uint32)


def _check_width(width: int, seed: int) -> None:
    rows = _rows(width, seed)
    lo, hi = fingerprint_u32v(rows, np)
    batch = [(int(h) << 32) | int(l) for l, h in zip(lo, hi)]
    for row, want in zip(rows, batch):
        got = fingerprint_row_host(row)
        assert got == want == _limb_fp(row), (width, row.tolist())
        assert 0 < got <= MASK64


@pytest.mark.parametrize("width", [1, 2, 16, 32])
def test_host_fold_matches_limb_fold(width):
    _check_width(width, seed=width)


@pytest.mark.parametrize("name", [spec.name for spec in ENCODINGS])
def test_host_fold_matches_at_bundled_widths(name):
    enc = next(s for s in ENCODINGS if s.name == name).factory()
    _check_width(enc.width, seed=1000 + enc.width)
    for row in np.asarray(enc.init_vecs(), np.uint32):
        assert fingerprint_row_host(row) == _limb_fp(row)


def test_host_fold_takes_any_row_shape_of_one_row():
    row = np.arange(5, dtype=np.uint32)
    want = _limb_fp(row)
    assert fingerprint_row_host(row.reshape(1, -1)) == want
    assert fingerprint_row_host(row.tolist()) == want


@pytest.mark.parametrize("lane", [0, 7, 0xFFFFFFFF])
def test_zero_digest_is_reserved_in_both_folds(monkeypatch, lane):
    # splitmix64(0) == 0, so a seed equal to the first lane's offset
    # sum folds a one-lane row to 0: both folds must answer 1.
    monkeypatch.setattr(fpmod, "_SEED", (lane + fpmod._golden(0)) & MASK64)
    row = np.array([lane], np.uint32)
    assert fingerprint_row_host(row) == 1
    assert _limb_fp(row) == 1
    assert fingerprint_row_host(np.array([lane ^ 1], np.uint32)) == _limb_fp(
        np.array([lane ^ 1], np.uint32)
    ) != 1


# -- through the sort-merge engine ----------------------------------------


@pytest.fixture(scope="module")
def traced_2pc():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys
    from stateright_tpu.telemetry import RunTracer

    tr = RunTracer()
    with tr.activate():
        checker = (
            TwoPhaseSys(rm_count=3)
            .checker()
            .spawn_tpu_sortmerge(
                capacity=1 << 10,
                frontier_capacity=256,
                cand_capacity=1024,
                track_paths=True,
            )
            .join()
        )
        paths = checker.discoveries()
    return checker, paths, tr.events


def test_sortmerge_vec_fp_clamps_all_ones(traced_2pc, monkeypatch):
    from stateright_tpu.checkers import tpu

    checker = traced_2pc[0]
    row = np.array([3, 1], np.uint32)
    assert checker._vec_fp(row) == _limb_fp(row)
    monkeypatch.setattr(tpu, "fingerprint_row_host", lambda r: MASK64)
    assert checker._vec_fp(row) == (0xFFFFFFFE << 32) | 0xFFFFFFFF
    monkeypatch.setattr(tpu, "fingerprint_row_host", lambda r: MASK64 - 1)
    assert checker._vec_fp(row) == MASK64 - 1


def test_2pc_paths_replay_and_decode_span_counts(traced_2pc):
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    checker, paths, events = traced_2pc
    model = TwoPhaseSys(rm_count=3)
    assert checker.unique_state_count() == 288
    assert set(paths) == {"abort agreement", "commit agreement"}

    host = model.checker().spawn_bfs().join().discoveries()
    props = {p.name: p for p in model.properties()}
    for name, path in paths.items():
        steps = path.steps
        # shortest depth, as the host BFS finds it
        assert len(path) == len(host[name])
        assert steps[0][0] in model.init_states()
        for (state, action), (nxt, _) in zip(steps, steps[1:]):
            assert action in model.actions(state)
            assert model.next_state(state, action) == nxt
        assert steps[-1][1] is None
        assert props[name].condition(model, steps[-1][0])

    decodes = [
        e for e in events
        if e["ev"] == "span" and e["phase"] == "cex_host_decode"
    ]
    assert sorted(d["steps"] for d in decodes) == sorted(
        len(p) for p in paths.values()
    )
    for d in decodes:
        # at least one successor fingerprinted per step after the init
        assert d["replay_fingerprints"] >= d["steps"] - 1 > 0
