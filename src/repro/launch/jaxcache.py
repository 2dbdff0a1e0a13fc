"""Persistent XLA compilation cache plumbing (DESIGN.md deviation #4).

Bucketed plan compilation shrinks the number of distinct XLA compiles to
one per bucket signature — but each of those still recurs on every process
restart. JAX's persistent compilation cache keeps the compiled executables
on disk, so a restarted server or a re-run benchmark pays a cache *read*
instead of a compile.

Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX
reads it itself; no other directory is set in code), otherwise one fixed
path inside the checkout, ``<checkout>/.jax_cache``. The path is part of
the cache's key, so it never moves: no temp, pid or time-based directory.
Entry points (``launch/serve.py``, ``chip_smoke.py``, the benchmarks) call
:func:`enable_compilation_cache` before their first compile; importing a
module never turns the cache on.

The same directory also hosts ``warmset.json`` — the speculative
warm-start record for the async compile service (DESIGN.md §8): the
bucket signatures an engine has served, persisted across restarts so the
next launch can pre-submit their compile jobs *before* the first request
arrives. The XLA cache holds the artifact; the warmset holds the intent.
"""

from __future__ import annotations

import json
import os
import warnings

QUARANTINE_SUBDIR = "_quarantine"
WARMSET_NAME = "warmset.json"
# JAX's executable entries; the directory also holds JAX's zero-byte
# ``.lockfile`` and ``-atime`` stamps, which are not entries.
ENTRY_SUFFIX = "-cache"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/jaxcache.py -> <checkout>/.jax_cache
DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def cache_dir() -> str:
    """The persistent cache directory: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``<checkout>/.jax_cache``."""
    return os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR


def warmset_path(cache_dir: str) -> str:
    return os.path.join(cache_dir, WARMSET_NAME)


def load_warmset(cache_dir: str) -> dict:
    """Read the warm-start descriptor set next to the XLA cache; a missing
    or corrupt file yields ``{}`` (cold start) — warm-start is a speedup,
    never a launch blocker."""
    path = warmset_path(cache_dir)
    try:
        with open(path) as f:
            ws = json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as e:
        warnings.warn(f"ignoring corrupt warmset {path!r}: {e}",
                      RuntimeWarning, stacklevel=2)
        return {}
    if not isinstance(ws, dict):
        warnings.warn(f"ignoring malformed warmset {path!r} "
                      f"(expected an object)", RuntimeWarning, stacklevel=2)
        return {}
    return ws


def save_warmset(cache_dir: str, warmset: dict) -> str | None:
    """Atomically persist an engine's ``warmset()`` payload (tmp +
    ``os.replace``, same discipline as checkpoints — a crash mid-write must
    not leave a truncated file for ``load_warmset`` to trip on)."""
    if not cache_dir:
        return None
    try:
        os.makedirs(cache_dir, exist_ok=True)
        path = warmset_path(cache_dir)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(warmset, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return path
    except OSError as e:
        warnings.warn(f"could not persist warmset in {cache_dir!r}: {e}",
                      RuntimeWarning, stacklevel=2)
        return None


def audit_cache_dir(cache_dir: str) -> list[str]:
    """Sweep a persistent cache directory for corrupt entries before JAX
    reads them: zero-byte or unreadable ``*-cache`` entries (the residue of
    a crash or a full disk mid-write) are moved into a ``_quarantine/``
    subdirectory — the entry recompiles fresh instead of poisoning the
    serve launcher.
    Returns the quarantined paths (empty on a healthy dir)."""
    quarantined: list[str] = []
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return quarantined   # missing dir: JAX creates it on first write
    qdir = os.path.join(cache_dir, QUARANTINE_SUBDIR)
    for name in names:
        path = os.path.join(cache_dir, name)
        if not name.endswith(ENTRY_SUFFIX) or not os.path.isfile(path):
            continue
        bad = None
        try:
            if os.path.getsize(path) == 0:
                bad = "zero-byte entry"
            else:
                with open(path, "rb") as f:
                    f.read(1)
        except OSError as e:
            bad = f"unreadable entry ({e})"
        if bad is None:
            continue
        try:
            os.makedirs(qdir, exist_ok=True)
            dest = os.path.join(qdir, name)
            os.replace(path, dest)
            quarantined.append(dest)
            warnings.warn(
                f"quarantined corrupt XLA cache entry {path!r} ({bad}); "
                f"it will recompile fresh", RuntimeWarning, stacklevel=2)
        except OSError as e:
            warnings.warn(
                f"could not quarantine corrupt XLA cache entry {path!r}: "
                f"{e}", RuntimeWarning, stacklevel=2)
    return quarantined


def enable_compilation_cache() -> bool:
    """Turn on JAX's persistent compilation cache at :func:`cache_dir`.

    Call before the process's first compile: JAX decides once whether the
    cache is in use. With ``$JAX_COMPILATION_CACHE_DIR`` set, JAX has
    already read the directory and only the size gates are set here.
    The min-compile-time/min-entry-size gates are zeroed so even the toy
    CI-sized programs are cached — the whole point here is surviving
    process restarts, not saving disk. A pre-existing dir is audited
    first: corrupt/truncated entries are quarantined so the launcher falls
    through to a fresh compile instead of crashing. Returns False (with a
    warning) when the path is not a usable directory.
    """
    path = cache_dir()
    if os.path.exists(path) and not os.path.isdir(path):
        warnings.warn(
            f"persistent compilation cache path {path!r} exists but is "
            f"not a directory; continuing without the cache",
            RuntimeWarning, stacklevel=2)
        return False
    audit_cache_dir(path)
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return True
