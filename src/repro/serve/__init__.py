"""Serving: the unified engine factory (`create_engine`), the online
read/write serving front-end with versioned snapshot reads
(`ServingFrontend`), host-offloaded embedding stores, chunked task
scheduling with shard-embedding reuse, LM decode loop.

Exports resolve lazily (PEP 562): ``repro.core.backend`` imports
``repro.serve.staging`` at module load, so an eager ``from .api import …``
here would close an import cycle through the partially-initialized core
package.
"""
from __future__ import annotations

_API = ("create_engine", "EngineConfig", "BACKENDS", "ChunkedRTECEngine",
        "serving_frontend", "FusionConfig")
_FRONTEND = ("ServingFrontend", "ReadTicket", "ReadRound",
             "ReadRejectedError", "StaleVersionError")
_CACHE = ("CacheConfig", "CacheStats", "HotRowCache")
_STAGING = ("StagingConfig",)

__all__ = list(_API + _FRONTEND + _CACHE + _STAGING)


def __getattr__(name: str):
    if name in _API:
        from repro.serve import api

        return getattr(api, name)
    if name in _FRONTEND:
        from repro.serve import frontend

        return getattr(frontend, name)
    if name in _CACHE:
        from repro.serve import hotcache

        return getattr(hotcache, name)
    if name in _STAGING:
        from repro.serve import staging

        return getattr(staging, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
