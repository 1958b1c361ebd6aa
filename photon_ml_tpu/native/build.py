"""Lazy g++ compilation of the native components.

One .so per translation unit, cached next to the source under a name that
carries a hash of the source's CONTENT (and of the build flags): a copy or a
fresh clone of the tree rebuilds exactly when the source it holds differs
from what the library was built from.  File times say nothing after a copy.
No pybind11 in this image — C ABI + ctypes only (plain-C signatures keep the
boundary trivially stable).
"""

from __future__ import annotations

import contextlib
import glob
import hashlib
import logging
import os
import subprocess
import tempfile
from typing import Optional

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_FLAGS = ["-O2", "-shared", "-fPIC", "-std=c++17", "-Wall"]
# per-translation-unit link flags
_EXTRA = {"avro_loader": ["-lz"]}


def library_path(name: str) -> str:
    """native/_lib<name>.<source hash>.so for the source as it is now."""
    h = hashlib.sha256(" ".join(_FLAGS + _EXTRA.get(name, [])).encode())
    with open(os.path.join(_DIR, f"{name}.cpp"), "rb") as f:
        h.update(f.read())
    return os.path.join(_DIR, f"_lib{name}.{h.hexdigest()[:16]}.so")


def compile_library(name: str, force: bool = False) -> Optional[str]:
    """Compile native/<name>.cpp -> its ``library_path``; None if unavailable.

    Reuses the library only when one built from this very source exists.
    Compiles to a temp file then renames (atomic on POSIX) so concurrent
    processes never load a half-written library; libraries of older sources
    are removed.
    """
    src = os.path.join(_DIR, f"{name}.cpp")
    if not os.path.exists(src):
        return None
    out = library_path(name)
    if not force and os.path.exists(out):
        return out
    tmp = None
    try:
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
        os.close(fd)
        subprocess.run(["g++", *_FLAGS, "-o", tmp, src, *_EXTRA.get(name, [])],
                       check=True, capture_output=True, text=True)
        os.replace(tmp, out)
        for stale in glob.glob(os.path.join(_DIR, f"_lib{name}*.so")):
            if stale != out:
                with contextlib.suppress(FileNotFoundError):  # a racing twin
                    os.unlink(stale)
        return out
    except (subprocess.CalledProcessError, OSError) as e:
        # OSError covers both a missing g++ and an unwritable package dir —
        # either way the pure-python fallback takes over.
        stderr = getattr(e, "stderr", "") or str(e)
        logger.warning("native build of %s failed (pure-python fallback): %s",
                       name, stderr.strip()[:500])
        if tmp is not None and os.path.exists(tmp):
            os.unlink(tmp)
        return None
