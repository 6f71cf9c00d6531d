"""Persistent plan cache (DESIGN.md §7): process-level LRU in front of an
on-disk JSON file, so a tuned decision survives the process — "tune
once, serialize, serve from cache".

Layout: one JSON file per *environment fingerprint* under the cache
directory (``$REPRO_PLAN_CACHE_DIR``, else ``$XDG_CACHE_HOME/repro/
plans``, else ``~/.cache/repro/plans``), named
``<fingerprint-hash>.json``.  The fingerprint hashes the plan schema
version, jax version, backend, and device kind — any of those changing
silently switches to a fresh file, which IS the invalidation rule: a
plan tuned on one stack never leaks onto another.  Inside the file,
plans are keyed by ``spec|dtype|backend`` (:meth:`ConvPlan.cache_key`).
Partitioned plans are the one exception to persistence: the
fingerprint does not cover mesh topology, so they stay in the process
LRU and never reach disk.

Disk I/O is strictly best-effort: an unreadable/unwritable cache
directory degrades to memory-only (the LRU), never to an error — the
planner must work in read-only containers and sandboxes.
"""
from __future__ import annotations

import collections
import hashlib
import json
import os
import pathlib
import tempfile
import threading
from typing import Dict, Optional

from repro.plan.convplan import PLAN_VERSION, ConvPlan

CACHE_DIR_ENV = "REPRO_PLAN_CACHE_DIR"
CACHE_FILE_VERSION = 1

_DEFAULT_MAX_ENTRIES = 4096


def environment_fingerprint() -> str:
    """Short stable hash of everything that invalidates cached plans."""
    import jax
    kind = jax.devices()[0].device_kind
    raw = (f"plan{PLAN_VERSION}|jax{jax.__version__}|"
           f"{jax.default_backend()}|{kind}")
    return hashlib.sha256(raw.encode()).hexdigest()[:16]


def plan_cache_dir() -> pathlib.Path:
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return pathlib.Path(env)
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = pathlib.Path(xdg) if xdg else pathlib.Path.home() / ".cache"
    return base / "repro" / "plans"


class PlanCache:
    """LRU of :class:`ConvPlan` backed by one fingerprinted JSON file.

    ``path=None`` resolves the default per-environment file lazily (so
    importing this module never touches jax or the filesystem);
    ``path=False``-y values other than None are taken literally.
    """

    def __init__(self, path: Optional[pathlib.Path] = None,
                 max_entries: int = _DEFAULT_MAX_ENTRIES):
        self._explicit_path = pathlib.Path(path) if path is not None else None
        self._path: Optional[pathlib.Path] = self._explicit_path
        self._mem: "collections.OrderedDict[str, ConvPlan]" = \
            collections.OrderedDict()
        self._max_entries = max_entries
        self._disk_loaded = False
        self._lock = threading.Lock()
        # Swallowed disk failures (unreadable, corrupt, read-only).  The
        # degradation stays silent per call, but operators need to see
        # it: the conv-service warmup surfaces this counter in the serve
        # report (DESIGN.md §9) instead of crashing — or hiding it.
        self.io_errors = 0

    # ----------------------------------------------------------- resolution

    def path(self) -> pathlib.Path:
        if self._path is None:
            self._path = plan_cache_dir() / f"{environment_fingerprint()}.json"
        return self._path

    def _load_disk_locked(self) -> None:
        if self._disk_loaded:
            return
        self._disk_loaded = True
        try:
            text = self.path().read_text()
        except FileNotFoundError:
            return            # a cache that simply isn't there yet is fine
        except OSError:
            self.io_errors += 1
            return
        try:
            doc = json.loads(text)
        except ValueError:
            self.io_errors += 1  # corrupt file: degrade, but count it
            return
        if doc.get("plan_cache_version") != CACHE_FILE_VERSION:
            return
        for key, plan_doc in doc.get("plans", {}).items():
            if key in self._mem:
                continue  # memory (newer) wins over disk
            try:
                self._mem[key] = ConvPlan.from_dict(plan_doc)
            except (ValueError, KeyError, TypeError):
                continue  # one stale entry never poisons the rest
        self._trim_locked()

    def _trim_locked(self) -> None:
        while len(self._mem) > self._max_entries:
            self._mem.popitem(last=False)

    def _flush_locked(self) -> None:
        # Partitioned plans never reach disk: the file's environment
        # fingerprint does not cover mesh topology, so a plan recording
        # mesh axes from one job must not resurface in another whose
        # mesh names differ.  They live in the process LRU only.
        doc = {
            "plan_cache_version": CACHE_FILE_VERSION,
            "plans": {k: p.to_dict() for k, p in self._mem.items()
                      if p.partition is None},
        }
        path = self.path()
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=str(path.parent),
                                       prefix=path.name, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            self.io_errors += 1  # read-only environment: memory-only now

    # ------------------------------------------------------------------ api

    def get(self, key: str) -> Optional[ConvPlan]:
        with self._lock:
            if key not in self._mem:
                self._load_disk_locked()
            plan = self._mem.get(key)
            if plan is not None:
                self._mem.move_to_end(key)
            return plan

    def put(self, key: str, plan: ConvPlan) -> None:
        with self._lock:
            self._load_disk_locked()  # merge before rewrite, not clobber
            self._mem[key] = plan
            self._mem.move_to_end(key)
            self._trim_locked()
            self._flush_locked()

    def clear(self) -> None:
        """Drop the memory tier and delete the disk file (tests; and the
        documented answer to 'my costmodel changed, flush the plans')."""
        with self._lock:
            self._mem.clear()
            self._disk_loaded = False
            try:
                self.path().unlink()
            except OSError:
                pass

    def __len__(self) -> int:
        with self._lock:
            return len(self._mem)


_global_cache: Optional[PlanCache] = None
_global_lock = threading.Lock()


def global_plan_cache() -> PlanCache:
    """The process-level cache ``plan_conv2d(mode="cached")`` and the
    ``conv2d`` auto path share."""
    global _global_cache
    with _global_lock:
        if _global_cache is None:
            _global_cache = PlanCache()
        return _global_cache


def reset_global_plan_cache() -> None:
    """Forget the process-level cache object (tests point the cache at a
    fresh tmpdir by resetting + setting REPRO_PLAN_CACHE_DIR)."""
    global _global_cache
    with _global_lock:
        _global_cache = None
