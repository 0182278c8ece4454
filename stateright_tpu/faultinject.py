"""Deterministic fault injection: the harness that proves the
checkpoint/resume path (stateright_tpu/checkpoint.py) actually
recovers.

A robustness claim without a way to trigger the failure is a
docstring; this module makes every cell of the crash matrix
(tools/crash_matrix.py) a *seeded, reproducible* event:

* **process kill at a chunk boundary** — the engine calls
  :func:`fire` at the two seams a real preemption lands on (the
  per-chunk sync boundary, and mid-chunk between dispatch and the
  stats readback); an armed ``kill`` fault ``os._exit``\\ s there, the
  way a preempted VM or an OOM-killer does (no atexit, no flushed
  trace — the resumed process's artifacts are the record, exactly as
  in production);
* **mid-chunk device exception** — an armed ``raise`` fault throws
  :class:`InjectedFault` at the same seams, modeling a device error
  surfacing through the XLA dispatch/readback path; the supervisor
  (checkpoint.supervised_run) treats it like any other device fault
  and retries from the last snapshot;
* **torn / corrupt snapshot** — :func:`corrupt_snapshot` truncates or
  bit-flips a written snapshot file, which resume must *detect*
  (zip CRC or the manifest's per-buffer checksum) and refuse with
  ``SnapshotCorruptError`` — never a silent wrong answer;
* **stale manifest** — :func:`stale_manifest` rewrites the snapshot's
  manifest (wrong git SHA, wrong encoding fingerprint) with VALID
  buffer checksums, which resume must refuse with
  ``SnapshotStaleError``;
* **persistent per-shard device fault** (the degrade-and-continue
  round) — a ``shard_fault`` armed with a shard id raises
  :class:`InjectedShardFault` at EVERY chunk at or past its armed
  chunk *as long as the faulted shard is still in the run's mesh*
  (the engines pass their live shard-id set to :func:`fire`): the
  model of a chip that died and stays dead. The supervisor's
  :class:`~stateright_tpu.checkpoint.FailurePolicy` sees the same
  shard fail across retries, classifies it persistent, and degrades
  the run onto the surviving shards — after which the fault stops
  firing, exactly as a dropped chip stops mattering;
* **chunk-dispatch hang** — a ``hang`` sleeps ``hang_sec`` (default
  30 s) at the dispatch site instead of raising: the shape of a
  chunk that never syncs (the XLA:CPU chunk stalls of ROADMAP D5),
  which no exception path ever surfaces. Only the hung-dispatch
  watchdog (checkers/tpu.py, ``watchdog_factor``) can see it;
* **collective-seam raise** — a ``raise`` armed at the
  ``collective_seam`` site fires only on mesh engines, just before
  the sharded dispatch: a device error surfacing from the all_to_all
  path, which the supervisor must treat like any chunk fault.

Faults arm either programmatically (:func:`arm`, in-process tests) or
via the ``STPU_FAULTS`` environment variable (subprocess kill cells):
a comma-separated list of ``<action>@<site>:<chunk>[:<arg>]`` specs,
e.g. ``STPU_FAULTS="kill@chunk_boundary:2"``,
``STPU_FAULTS="raise@mid_chunk:1"``,
``STPU_FAULTS="hang@mid_chunk:1:20"`` (arg = seconds), or
``STPU_FAULTS="shard_fault@mid_chunk:1:0"`` (arg = shard id). Sites
are ``chunk_boundary`` (fires AFTER the chunk's snapshot write, so a
kill there proves the committed-snapshot sequencing), ``mid_chunk``
(fires after the async dispatch, before the stats readback), and
``collective_seam`` (mesh engines only, before the sharded dispatch).
Each armed fault fires ONCE by default, so a supervised retry doesn't
re-trip it — except ``shard_fault``, which is persistent by design.

Every firing emits a ``fault_injected`` telemetry event (best effort:
a ``kill`` loses the in-memory trace with the process, as a real kill
would). Import-light (stdlib only) so tools and tests load it without
jax.
"""

from __future__ import annotations

import os
from typing import Optional

SITES = ("chunk_boundary", "mid_chunk", "collective_seam")
ACTIONS = ("raise", "kill", "hang", "shard_fault")

#: exit code of an injected process kill (mirrors SIGKILL's 128+9 so
#: drivers distinguish the injected death from an assertion failure).
KILL_EXIT_CODE = 137

#: default sleep of an injected dispatch hang (long enough that any
#: sanely derived watchdog deadline expires first; a daemonized hang
#: thread dies with the process, so a recovered run never waits it
#: out).
DEFAULT_HANG_SEC = 30.0


class InjectedFault(RuntimeError):
    """A deterministically injected fault (``raise`` action). Carries
    the site and chunk so the supervisor's recovery warning names what
    fired. Deliberately NOT matched by the auto-budget retry (its
    message never mentions a buffer overflow): injected faults are the
    supervisor's to handle."""

    def __init__(self, site: str, chunk: int):
        super().__init__(
            f"injected fault at {site} (chunk {chunk}) — "
            "stateright_tpu/faultinject.py"
        )
        self.site = site
        self.chunk = chunk


class InjectedShardFault(InjectedFault):
    """A persistent per-shard device fault (``shard_fault`` action):
    the model of one dead chip in a mesh. Carries the shard id so the
    supervisor's :class:`~stateright_tpu.checkpoint.FailurePolicy`
    can attribute repeated failures to the same shard and escalate to
    an elastic degrade."""

    def __init__(self, site: str, chunk: int, shard: int):
        RuntimeError.__init__(
            self,
            f"injected persistent device fault on shard {shard} at "
            f"{site} (chunk {chunk}) — stateright_tpu/faultinject.py"
        )
        self.site = site
        self.chunk = chunk
        self.shard = int(shard)


_ARMED: list[dict] = []
_ENV_PARSED = False


def parse_spec(spec: str) -> dict:
    """One ``<action>@<site>:<chunk>[:<arg>]`` spec -> an armed-fault
    dict. The optional trailing arg is the hang duration in seconds
    (``hang``) or the faulted shard id (``shard_fault``)."""
    try:
        action, rest = spec.split("@", 1)
        parts = rest.split(":")
        site = parts[0]
        chunk_i = int(parts[1])
        arg = parts[2] if len(parts) > 2 else None
        if len(parts) > 3:
            raise ValueError("too many fields")
    except (ValueError, IndexError) as exc:
        raise ValueError(
            f"bad fault spec {spec!r} (want "
            "<action>@<site>:<chunk>[:<arg>], e.g. "
            "kill@chunk_boundary:2, hang@mid_chunk:1:20, "
            "shard_fault@mid_chunk:1:0)"
        ) from exc
    if action not in ACTIONS:
        raise ValueError(f"unknown fault action {action!r} (use one of "
                         f"{ACTIONS})")
    if site not in SITES:
        raise ValueError(f"unknown fault site {site!r} (use one of "
                         f"{SITES})")
    f = dict(action=action, site=site, chunk=chunk_i, once=True)
    if action == "hang":
        f["hang_sec"] = (float(arg) if arg is not None
                         else DEFAULT_HANG_SEC)
    elif action == "shard_fault":
        # persistent by design: a dead chip stays dead until the run
        # degrades away from it
        f["shard"] = int(arg) if arg is not None else 0
        f["once"] = False
    elif arg is not None:
        raise ValueError(
            f"fault spec {spec!r}: trailing arg is only meaningful "
            "for hang (seconds) and shard_fault (shard id)"
        )
    return f


def arm(action: str, site: str, chunk: int, once: bool = True,
        shard: Optional[int] = None,
        hang_sec: Optional[float] = None) -> None:
    """Arm one fault programmatically (tests / the crash matrix).
    ``shard_fault`` faults are always persistent — a dead chip stays
    dead until the run degrades away from it (``once`` is ignored)."""
    if action not in ACTIONS:
        raise ValueError(f"unknown fault action {action!r}")
    if site not in SITES:
        raise ValueError(f"unknown fault site {site!r}")
    f = dict(action=action, site=site, chunk=int(chunk), once=once)
    if action == "shard_fault":
        f["shard"] = int(shard or 0)
        f["once"] = False
    if action == "hang":
        f["hang_sec"] = float(
            hang_sec if hang_sec is not None else DEFAULT_HANG_SEC
        )
    _ARMED.append(f)


def disarm_all() -> None:
    """Clear every armed fault (test teardown)."""
    _ARMED.clear()


def armed() -> list[dict]:
    """The currently armed faults (read-only copies)."""
    _parse_env()
    return [dict(f) for f in _ARMED]


def _parse_env() -> None:
    global _ENV_PARSED
    if _ENV_PARSED:
        return
    _ENV_PARSED = True
    env = os.environ.get("STPU_FAULTS", "").strip()
    if not env:
        return
    for spec in env.split(","):
        spec = spec.strip()
        if spec:
            _ARMED.append(parse_spec(spec))


def chunk_for_seed(seed: int, n_chunks: int) -> int:
    """Deterministic chunk pick for a seeded matrix cell: an LCG step
    over the seed folded into [0, n_chunks) — stable across platforms
    (no RNG library), so ``crash_matrix --seed`` reproduces the exact
    kill point."""
    if n_chunks <= 0:
        return 0
    return (seed * 1103515245 + 12345) % n_chunks


def fire(site: str, chunk: int, shards=None) -> None:
    """The engine-side hook (checkers/tpu.py chunk loop): fires the
    first armed fault matching (site, chunk). ``raise`` throws
    :class:`InjectedFault`; ``kill`` emits the telemetry event (lost
    with the process, as a real kill's would be) and ``os._exit``\\ s
    with :data:`KILL_EXIT_CODE`; ``hang`` sleeps its armed duration
    (the watchdog's territory — no exception ever surfaces);
    ``shard_fault`` raises :class:`InjectedShardFault` at EVERY chunk
    at or past its armed chunk, as long as its shard id appears in
    ``shards`` (the engine's live shard-id set — None means
    single-chip/unfiltered, where shard 0 is the only shard). No
    armed faults = a list check and out (the hook is per-chunk, not
    per-wave — cost is noise)."""
    _parse_env()
    if not _ARMED:
        return
    for f in _ARMED:
        if f["site"] != site:
            continue
        if f["action"] == "shard_fault":
            # persistent: the chunk is a first-fire threshold, and a
            # degraded mesh that dropped the shard stops matching
            if chunk < f["chunk"]:
                continue
            if shards is not None and f["shard"] not in shards:
                continue
            if shards is None and f["shard"] != 0:
                continue
        elif f["chunk"] != chunk:
            continue
        if f["once"]:
            _ARMED.remove(f)
        from . import telemetry

        telemetry.emit(
            "fault_injected", site=site, chunk=int(chunk),
            action=f["action"],
            **({"shard": f["shard"]}
               if f["action"] == "shard_fault" else {}),
        )
        if f["action"] == "kill":
            # A real preemption: no cleanup, no atexit, no flushed
            # buffers. os._exit is the honest model.
            os._exit(KILL_EXIT_CODE)
        if f["action"] == "hang":
            # a wedged dispatch: nothing raises
            import time

            time.sleep(f["hang_sec"])
            return
        if f["action"] == "shard_fault":
            raise InjectedShardFault(site, chunk, f["shard"])
        raise InjectedFault(site, chunk)


# -- snapshot-damage helpers (the torn/stale matrix cells) ----------------


def corrupt_snapshot(path: str, mode: str = "truncate",
                     seed: int = 0) -> None:
    """Damage a written snapshot in place, deterministically:

    * ``truncate`` — keep only the first half of the file (a crash
      mid-write on a filesystem without the atomic-rename guarantee,
      or a partial copy);
    * ``flip`` — flip bits at several seed-jittered offsets across
      the MIDDLE HALF of the file (silent media corruption; buffer
      payloads dominate a snapshot, so the flips land in checksummed
      data — a flip in the zip's redundant structural bytes alone
      would be semantically harmless, which is not the cell this
      models).

    Resume must detect either (``SnapshotCorruptError``)."""
    size = os.path.getsize(path)
    if mode == "truncate":
        with open(path, "r+b") as fh:
            fh.truncate(max(size // 2, 1))
        return
    if mode == "flip":
        base = size // 4 + (seed * 2654435761) % max(size // 16, 1)
        step = max(size // 16, 1)
        with open(path, "r+b") as fh:
            for k in range(8):
                off = min(base + k * step, size - 1)
                fh.seek(off)
                b = fh.read(1)
                fh.seek(off)
                fh.write(bytes([b[0] ^ 0x10]))
        return
    raise ValueError(f"unknown corruption mode {mode!r} "
                     "(use truncate|flip)")


def stale_manifest(path: str, field: str = "git_sha",
                   value: Optional[str] = None) -> None:
    """Rewrite a snapshot's manifest field (buffer checksums stay
    VALID — this is the stale cell, not the torn cell): ``git_sha``
    models resuming onto a different commit, ``encoding`` models
    resuming into a different model/encoding. Resume must refuse with
    ``SnapshotStaleError``."""
    from . import checkpoint

    manifest, buffers = checkpoint._read_raw(path)
    if field == "git_sha":
        manifest["git_sha"] = value or "0" * 40
    elif field == "encoding":
        manifest["encoding"] = value or "bogus-encoding/W0/K0"
    else:
        raise ValueError(f"unknown stale field {field!r} "
                         "(use git_sha|encoding)")
    checkpoint._write_file(path, manifest, buffers)
