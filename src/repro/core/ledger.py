"""Run ledger: durable checkpoint/resume state for ``run_grid`` sweeps.

Long sweeps (auto-tuner searches, 10^5-request serving replays) must
survive a crash without discarding completed work. The ledger is the
on-disk flight recorder that makes that possible:

    results/runs/<run_id>/
        manifest.json           # grid hash/doc, engine, status
        chunks/<key>.json       # one shard per completed chunk
        resplits/<key>.json     # budget-blown chunks split into children

Each *shard* holds the serialized results of one fault-isolated chunk
(per-limit subcell results for the batched engine, whole-cell records
for the scalar path), written atomically (temp + ``os.replace``) only
after the chunk fully succeeds. ``run_grid(..., resume=run_id)`` loads
every shard whose key matches the new run's chunk plan, re-runs the
rest, and reassembles by (cell index, limit ordinal) — so the final
records are **bit-identical** to an uninterrupted run (JSON floats are
serialized via ``repr`` and round-trip doubles exactly; the property
tests in ``tests/test_ledger.py`` pin this).

Chunk keys are *content-addressed* — a hash of the global (cell, limit
ordinal) ids a chunk covers — not positional. A resume with a
different worker count shards the plan differently; keys that still
match are reused, the rest re-run. Correctness never depends on the
plans matching, only the grid hash must (validated at open).

The manifest's ``status`` walks ``pending`` (created, nothing ran) /
``running`` → ``complete`` / ``partial`` (quarantined failures) /
``truncated`` (deadline hit). A crash leaves ``running`` — resumable,
and repaired to ``interrupted`` once the run's files have been silent
for ``stale_after`` seconds (see :meth:`RunLedger.probe_status`).

One process drives a run at a time.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pathlib
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from repro.core import faults
from repro.core.gpu import GPUResult
from repro.core.simulator import SimResult

LEDGER_SCHEMA = 1
DEFAULT_ROOT = "results/runs"

# a run's only signs of life are its file writes, so a "running"
# manifest is repaired to "interrupted" only after this much silence
# unless a tighter bound is requested.
DEFAULT_STALE_AFTER = 600.0


def runs_root() -> pathlib.Path:
    """Ledger root directory; ``$REPRO_RUNS_DIR`` overrides."""
    return pathlib.Path(os.environ.get("REPRO_RUNS_DIR", "") or DEFAULT_ROOT)


def grid_hash(grid) -> str:
    """Identity hash of an :class:`~repro.core.runner.ExperimentGrid`:
    everything that determines the records (workloads, policies, config
    reprs, scale, seed, GPU shape, sweep limits). Two grids with equal
    hashes produce bit-identical records, so resuming across them is
    sound; a mismatch at resume is refused."""
    doc = {
        "name": grid.name,
        "workloads": list(grid.workloads),
        "policies": list(grid.policies),
        "variants": {k: repr(v) for k, v in (grid.variants or {}).items()},
        "scale": repr(grid.scale),
        "seed": grid.seed,
        "gpu": repr(grid.gpu),
        "best_swl_limits": list(grid.best_swl_limits),
    }
    blob = json.dumps(doc, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


def chunk_key(item_ids: Sequence[str]) -> str:
    """Content-addressed shard key: hash of the sorted global item ids
    (``"<cell>:<limit ordinal>"`` for batched subcells, ``"cell:<i>"``
    for scalar-path cells) this chunk covers."""
    blob = "\n".join(sorted(item_ids)).encode()
    return hashlib.sha256(blob).hexdigest()[:20]


# ------------------------------------------------------- result serializers
# json.dumps writes floats via repr (shortest round-trip form), and
# json.loads parses back the identical double — so doc round-trips are
# bit-exact. The only lossy container is JSON's lack of tuples:
# SimResult.timeline holds (cycle, ipc, active) tuples, restored below.

def sim_to_doc(res: SimResult) -> dict:
    d = dataclasses.asdict(res)
    d["timeline"] = [list(t) for t in res.timeline]
    return d


def doc_to_sim(doc: dict) -> SimResult:
    d = dict(doc)
    d["timeline"] = [tuple(t) for t in d.get("timeline", [])]
    d["pairs"] = [list(p) for p in d.get("pairs", [])]
    return SimResult(**d)


def gpu_to_doc(res: GPUResult) -> dict:
    d = dataclasses.asdict(res)
    d["per_sm"] = [sim_to_doc(r) for r in res.per_sm]
    return d


def doc_to_gpu(doc: dict) -> GPUResult:
    d = dict(doc)
    d["per_sm"] = [doc_to_sim(r) for r in d.get("per_sm", [])]
    return GPUResult(**d)


def result_to_doc(res) -> dict:
    if isinstance(res, GPUResult):
        return {"kind": "gpu", "res": gpu_to_doc(res)}
    return {"kind": "sim", "res": sim_to_doc(res)}


def doc_to_result(doc: dict):
    if doc["kind"] == "gpu":
        return doc_to_gpu(doc["res"])
    return doc_to_sim(doc["res"])


class RunLedger:
    """One run's on-disk checkpoint state (see module docstring).

    Thread-safe: chunk workers save shards concurrently; each shard is
    an independent file and manifest writes are serialized."""

    def __init__(self, run_id: str, root: Optional[pathlib.Path] = None):
        if not run_id or "/" in run_id or run_id.startswith("."):
            raise ValueError(f"bad run id {run_id!r}")
        self.run_id = run_id
        self.dir = (root if root is not None else runs_root()) / run_id
        self.chunk_dir = self.dir / "chunks"
        self.resplit_dir = self.dir / "resplits"
        self.manifest_path = self.dir / "manifest.json"
        self._lock = threading.Lock()
        self.manifest: Dict[str, Any] = {}
        self.resumed_chunks = 0

    # ------------------------------------------------------------ lifecycle
    def open(self, manifest: Dict[str, Any], resume: bool = False,
             status: str = "running") -> None:
        """Start (or resume) the run. ``manifest`` must carry
        ``grid_hash``; on resume it is validated against the stored one
        and completed shards are kept. A non-resume open of an existing
        run id wipes stale shards/resplits — a fresh run must
        never absorb another grid's results.

        On resume of a run still marked ``running``, staleness is
        probed (the age of the run's newest file): an orphaned run —
        its process died without ``finish()`` — is recorded as an
        interruption rather than silently continuing the lie."""
        self.chunk_dir.mkdir(parents=True, exist_ok=True)
        prev = None
        if self.manifest_path.exists():
            try:
                prev = json.loads(self.manifest_path.read_text())
            except (OSError, ValueError):
                prev = None
        interruptions = 0
        created_ts = time.time()
        if resume:
            if prev is None:
                raise ValueError(
                    f"cannot resume run {self.run_id!r}: no manifest under "
                    f"{self.dir}")
            if prev.get("grid_hash") != manifest.get("grid_hash"):
                raise ValueError(
                    f"cannot resume run {self.run_id!r}: grid hash mismatch "
                    f"(ledger {prev.get('grid_hash')!r} vs current "
                    f"{manifest.get('grid_hash')!r}) — the grid changed "
                    "since the original run")
            interruptions = int(prev.get("interruptions", 0) or 0)
            created_ts = float(prev.get("created_ts", created_ts))
            if self._probe_stale(prev):
                interruptions += 1      # orphan detected: repair the record
        elif prev is not None:
            for sub in (self.chunk_dir, self.resplit_dir):
                if sub.is_dir():
                    for stale in sub.glob("*.json"):
                        try:
                            stale.unlink()
                        except OSError:
                            pass
        doc = dict(manifest)
        doc.update(schema=LEDGER_SCHEMA, run_id=self.run_id,
                   status=status, created_ts=created_ts,
                   interruptions=interruptions)
        self.manifest = doc
        self._write_manifest()

    def finish(self, status: str) -> None:
        """Seal the run: ``complete`` (all cells succeeded), ``partial``
        (quarantined failures), or ``truncated`` (deadline)."""
        with self._lock:
            self.manifest["status"] = status
        self._write_manifest()

    def _write_manifest(self) -> None:
        with self._lock:
            self.manifest["updated_ts"] = time.time()
            blob = json.dumps(self.manifest, indent=1, sort_keys=True)
        _atomic_write(self.manifest_path, blob)

    def load(self) -> Dict[str, Any]:
        """Read the on-disk manifest into ``self.manifest`` (for
        inspection tooling; no status change).
        Raises ``ValueError`` when the run does not exist."""
        try:
            self.manifest = json.loads(self.manifest_path.read_text())
        except (OSError, ValueError) as exc:
            raise ValueError(
                f"run {self.run_id!r} has no readable manifest under "
                f"{self.dir}: {exc}") from exc
        return self.manifest

    # --------------------------------------------------------------- shards
    def shard_path(self, key: str) -> pathlib.Path:
        return self.chunk_dir / f"{key}.json"

    def load_chunk(self, key: str) -> Optional[List[dict]]:
        """Items of a completed chunk, or ``None`` if absent/unreadable.
        A corrupt shard (torn write, bad disk) is deleted and treated as
        never-completed — the chunk simply re-runs."""
        path = self.shard_path(key)
        if not path.exists():
            return None
        try:
            doc = json.loads(path.read_text())
            if doc.get("schema") != LEDGER_SCHEMA:
                raise ValueError(f"shard schema {doc.get('schema')!r}")
            items = doc["items"]
            if not isinstance(items, list):
                raise ValueError("shard items not a list")
        except (OSError, ValueError, KeyError):
            try:
                path.unlink()
            except OSError:
                pass
            return None
        with self._lock:
            self.resumed_chunks += 1
        return items

    def save_chunk(self, key: str, items: List[dict]) -> None:
        """Atomically persist a *fully successful* chunk's items.
        Callers only shard chunks whose every item succeeded — failed or
        truncated chunks stay unrecorded so a resume retries them."""
        faults.fire("records.save", key=f"chunk:{key}",
                    path=str(self.shard_path(key)))
        blob = json.dumps({"schema": LEDGER_SCHEMA, "run": self.run_id,
                           "key": key, "items": items}, sort_keys=True)
        _atomic_write(self.shard_path(key), blob)

    def completed_keys(self) -> List[str]:
        if not self.chunk_dir.is_dir():
            return []
        return sorted(p.stem for p in self.chunk_dir.glob("*.json"))

    # ------------------------------------------------------------- resplits
    def save_resplit(self, parent_key: str,
                     children: List[List[str]]) -> None:
        """Record that budget-blown chunk ``parent_key`` was split into
        ``children`` (lists of global item ids). Deterministic content
        → a rerun that splits the same chunk writes identical bytes."""
        blob = json.dumps({"schema": LEDGER_SCHEMA, "run": self.run_id,
                           "parent": parent_key,
                           "children": [sorted(c) for c in children]},
                          sort_keys=True)
        _atomic_write(self.resplit_dir / f"{parent_key}.json", blob)

    def load_resplits(self) -> Dict[str, List[List[str]]]:
        """parent chunk key → recorded child item-id lists."""
        if not self.resplit_dir.is_dir():
            return {}
        out: Dict[str, List[List[str]]] = {}
        for path in sorted(self.resplit_dir.glob("*.json")):
            try:
                doc = json.loads(path.read_text())
                kids = doc["children"]
                if not isinstance(kids, list) or not kids:
                    raise ValueError("bad children")
            except (OSError, ValueError, KeyError):
                continue
            out[path.stem] = [list(map(str, c)) for c in kids]
        return out

    # ------------------------------------------------------------ staleness
    def last_activity_ts(self) -> float:
        """Most recent mtime across the manifest, shards and resplits —
        the run's last observable sign of life."""
        latest = 0.0
        paths = [self.manifest_path]
        for sub in (self.chunk_dir, self.resplit_dir):
            if sub.is_dir():
                paths.extend(sub.glob("*.json"))
        for p in paths:
            try:
                latest = max(latest, p.stat().st_mtime)
            except OSError:
                continue
        return latest

    def _probe_stale(self, manifest: Dict[str, Any],
                     stale_after: Optional[float] = None) -> bool:
        if manifest.get("status") != "running":
            return False
        if stale_after is None:
            stale_after = DEFAULT_STALE_AFTER
        return time.time() - self.last_activity_ts() > stale_after

    def probe_status(self, stale_after: Optional[float] = None) -> str:
        """The manifest status, with orphan detection: a ``running``
        run whose files have been silent for ``stale_after`` seconds
        (default :data:`DEFAULT_STALE_AFTER`) is really
        ``interrupted``."""
        if not self.manifest:
            self.load()
        status = str(self.manifest.get("status", "unknown"))
        if self._probe_stale(self.manifest, stale_after):
            return "interrupted"
        return status

    def repair_if_stale(self, stale_after: Optional[float] = None) -> bool:
        """Persist ``interrupted`` for an orphaned ``running`` run.
        Returns whether a repair happened."""
        if not self.manifest:
            self.load()
        if not self._probe_stale(self.manifest, stale_after):
            return False
        with self._lock:
            self.manifest["status"] = "interrupted"
            self.manifest["interrupted_ts"] = time.time()
            self.manifest["interruptions"] = \
                int(self.manifest.get("interruptions", 0) or 0) + 1
        self._write_manifest()
        return True

    def remove(self) -> None:
        """Delete the whole run directory (``runs gc``)."""
        shutil.rmtree(self.dir, ignore_errors=True)


def _atomic_write(path: pathlib.Path, text: str) -> None:
    """Unique temp + fsync + ``os.replace``: concurrent writers never
    collide on the temp name and a crash never leaves a torn file."""
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.parent / (f".{path.name}.{os.getpid()}"
                         f".{threading.get_ident()}.tmp")
    try:
        with open(tmp, "w") as fh:
            fh.write(text)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            tmp.unlink()
        except OSError:
            pass
        raise
