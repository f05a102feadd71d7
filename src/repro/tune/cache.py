"""Persistent per-(platform, N, K, D) tuning cache.

One JSON file maps problem signatures to their measured-best
:class:`~repro.core.engine.EngineConfig` plus the measurements that
justified it. Default location: ``~/.cache/repro_kmeans_tune.json``;
override with the ``REPRO_KMEANS_TUNE_CACHE`` environment variable or
an explicit ``TuneCache(path=...)``.

The cache is loaded once per process and written through on every
store, so a ``fit(tune="force")`` search and the fits that follow in
the same process always agree. A corrupt or version-mismatched file is
treated as empty (tuning is always safe to redo — it can never change
results, only wall-clock).
"""
from __future__ import annotations

import json
import os
import tempfile

from ..core.engine import EngineConfig

ENV_VAR = "REPRO_KMEANS_TUNE_CACHE"
VERSION = 1


def default_path() -> str:
    env = os.environ.get(ENV_VAR)
    if env:
        return os.path.expanduser(env)
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "repro_kmeans_tune.json")


class TuneCache:
    """Disk-backed signature -> tuned-config map (see module docstring).

    ``path=None`` resolves :func:`default_path` at construction time
    (so the env var is honoured per instance, not per import).
    """

    def __init__(self, path: str | None = None):
        self.path = path if path is not None else default_path()
        self._entries: dict | None = None        # lazy-loaded

    # -- persistence -------------------------------------------------------

    def load(self, reload: bool = False) -> dict:
        if self._entries is not None and not reload:
            return self._entries
        self._entries = {}
        try:
            with open(self.path) as fh:
                payload = json.load(fh)
            if isinstance(payload, dict) and \
                    payload.get("version") == VERSION:
                self._entries = dict(payload.get("entries", {}))
        except (FileNotFoundError, ValueError, OSError):
            pass
        return self._entries

    def save(self) -> None:
        payload = {"version": VERSION, "entries": self.load()}
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        # atomic-ish write: never leave a torn JSON behind for the next
        # process to choke on
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(self.path) or ".",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as fh:
                json.dump(payload, fh, indent=2, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- access ------------------------------------------------------------

    def entry(self, sig: str) -> dict | None:
        """Raw cache record (config + measurements) or None."""
        return self.load().get(sig)

    def lookup(self, sig: str) -> EngineConfig | None:
        e = self.entry(sig)
        if not e or "config" not in e:
            return None
        return EngineConfig.from_dict(e["config"])

    def store(self, sig: str, config: EngineConfig, **meta) -> None:
        self.load()[sig] = {"config": config.to_dict(), **meta}
        self.save()

    def drop(self, sig: str) -> None:
        if self.load().pop(sig, None) is not None:
            self.save()

    def clear(self) -> None:
        self._entries = {}
        self.save()

    def signatures(self) -> list:
        return sorted(self.load())


_default: TuneCache | None = None


def default_cache() -> TuneCache:
    """Process-wide cache singleton (what ``engine.fit`` consults)."""
    global _default
    if _default is None:
        _default = TuneCache()
    return _default


def set_default_cache(cache: TuneCache | str | None) -> TuneCache:
    """Replace the process-wide cache (tests / benchmark harnesses).
    Accepts a TuneCache, a path, or None to re-resolve the default."""
    global _default
    if isinstance(cache, str):
        cache = TuneCache(cache)
    _default = cache
    return default_cache()
