"""Shared on-demand builder/loader for the first-party C++ libraries.

One place owns the rules both loaders (codec, staging ring) need:

- **staleness**: a ``.so`` not strictly newer than its ``.cpp`` is
  rebuilt (``>=``: an edit in the same clock tick as the last build
  counts as stale) — a stale binary silently running old code is how
  the r5 lzb heap-overflow fix could have failed to take effect on
  machines with a pre-fix build;
- **no stale fallback**: if a needed rebuild fails, the caller gets
  ``False`` / ``None`` and takes its NumPy/Python path, NEVER the
  known-stale binary;
- **atomic install**: g++ writes a temp path that is ``os.replace``d
  into place, so concurrent builders (pytest workers, parallel
  processes) can never leave a half-written library for ``CDLL``;
- **said, not silent**: a loader that falls back prints once, on
  stderr, why and which path it took.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "_native")


def ensure_built(src: str, so_path: str, timeout: float = 120.0) -> bool:
    """True iff ``so_path`` exists and is strictly newer than ``src``
    (built here if it was not)."""
    if not os.path.exists(src):
        return os.path.exists(so_path)
    if os.path.exists(so_path) \
            and os.path.getmtime(src) < os.path.getmtime(so_path):
        return True
    tmp = f"{so_path}.build.{os.getpid()}"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-o", tmp,
             src],
            check=True, capture_output=True, timeout=timeout)
        os.replace(tmp, so_path)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        print(f"defer_tpu: building {os.path.basename(so_path)} failed: "
              f"{e!r} {detail.decode(errors='replace')[-400:]}",
              file=sys.stderr, flush=True)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def load_library(stem: str, lib_name: str, fallback: str):
    """``ctypes.CDLL`` of ``_native/<lib_name>`` built from
    ``_native/<stem>.cpp``, or None — after saying on stderr that the
    ``fallback`` path is taken instead."""
    so_path = os.path.join(NATIVE_DIR, lib_name)
    if ensure_built(os.path.join(NATIVE_DIR, f"{stem}.cpp"), so_path):
        try:
            return ctypes.CDLL(so_path)
        except OSError as e:
            print(f"defer_tpu: loading {lib_name} failed: {e!r}",
                  file=sys.stderr, flush=True)
    print(f"defer_tpu: native {stem} library unavailable; taking the "
          f"{fallback} path", file=sys.stderr, flush=True)
    return None
