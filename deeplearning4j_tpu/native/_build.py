"""Build-on-first-use for the native libraries, keyed to their source.

The built file's name carries a content hash of the sources it was built
from (``libtnd-<hash>.so``), so a binary left in the tree by an earlier
checkout — the ``*.so`` files are git-ignored, and a copied working tree
carries them along — is never loaded for sources it does not match: a
changed source simply resolves to a name that does not exist yet.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile
from typing import Callable, List, Optional, Sequence

SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "native")
_OUT_DIR = os.path.dirname(os.path.abspath(__file__))


def keyed_path(stem: str, sources: Sequence[str]) -> Optional[str]:
    """``<package>/native/<stem>-<sha256 of sources, 16 hex>.so``; None when
    a source file is missing (a wheel without the ``native/`` tree)."""
    h = hashlib.sha256()
    for name in sources:
        try:
            with open(os.path.join(SRC_DIR, name), "rb") as f:
                h.update(name.encode() + b"\0" + f.read() + b"\0")
        except OSError:
            return None
    return os.path.join(_OUT_DIR, f"{stem}-{h.hexdigest()[:16]}.so")


def build_or_reuse(stem: str, sources: Sequence[str],
                   command: Callable[[str], Optional[List[str]]],
                   timeout: float = 180.0) -> Optional[str]:
    """Path of the library built from exactly these sources, compiling it
    when no file of that name exists. ``command(out_path)`` returns the
    compiler argv (or None when a prerequisite is missing). None when the
    library cannot be built here."""
    path = keyed_path(stem, sources)
    if path is None:
        return None
    if os.path.exists(path):
        return path
    fd, tmp = tempfile.mkstemp(dir=_OUT_DIR, prefix=f".{stem}-", suffix=".so")
    os.close(fd)
    try:
        cmd = command(tmp)
        if cmd is None:
            return None
        subprocess.run(cmd, check=True, capture_output=True, timeout=timeout)
        os.replace(tmp, path)  # atomic: a racing process never loads half a file
    except (subprocess.SubprocessError, OSError):
        return None
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return path
