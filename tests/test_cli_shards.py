"""``paxos check-tpu N --shards S`` through the CLI entry point, on the
virtual CPU mesh, against the benchmark's plain reference of Paxos
(``benchmark/reference/paxos.py``, which imports nothing of the
program): the unique count, depth and discovered-property set of
upstream's ``paxos check 2`` (16,668 states) at every shard count."""

import importlib.util
import os

import jax
import pytest

from stateright_tpu import cli

REFERENCE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmark", "reference",
)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"plain_reference_{name}", os.path.join(REFERENCE, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference_paxos2():
    bfs, paxos = _load("bfs"), _load("paxos")
    return bfs.explore(paxos, {"client_count": 2, "server_count": 3})


def run_cli(argv):
    """``cli.main(argv)``; returns the checker the CLI built, captured at
    its reporting seam."""
    got = []
    cli._SESSION_HOOK.hook = got.append
    try:
        cli.main(argv)
    finally:
        cli._SESSION_HOOK.hook = None
    (checker,) = got
    return checker


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_sharded_check_matches_reference(shards, reference_paxos2, capsys):
    if shards > len(jax.devices()):
        pytest.skip("needs the virtual 8-device CPU mesh")
    checker = run_cli(["paxos", "check-tpu", "2", "--shards", str(shards)])
    out = capsys.readouterr().out
    assert f"over {shards} shards" in out
    assert "Done." in out
    assert checker.n_shards == shards
    assert reference_paxos2["unique"] == 16668
    assert checker.unique_state_count() == reference_paxos2["unique"]
    assert checker.max_depth() == reference_paxos2["depth"]
    assert checker.discovered_property_names() == set(
        reference_paxos2["discoveries"]
    )


def test_more_shards_than_devices_fails_at_once():
    n = len(jax.devices()) + 1
    with pytest.raises(SystemExit, match=(
            f"--shards {n}: n_shards={n} > {n - 1} available devices")):
        cli.main(["paxos", "check-tpu", "2", f"--shards={n}"])


@pytest.mark.parametrize("argv,message", [
    (["paxos", "check", "2", "--shards", "2"], "needs paxos check-tpu"),
    (["paxos", "check-tpu", "2", "--shards", "0"], "whole number"),
    (["paxos", "check-tpu", "3", "--shards", "2"], "per-shard budgets"),
])
def test_shards_refusals(argv, message):
    with pytest.raises(SystemExit, match=message):
        cli.main(argv)
