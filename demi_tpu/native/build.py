"""Build the native libraries from ``native/*.cpp``, keyed on source content.

A built library's file name carries the sha256 of the source it was
compiled from, so a ``.so`` found on disk is loaded only when it was built
from exactly the source in this checkout. An mtime says nothing of the
kind: a copied tree, a checkout of another commit or a restored backup all
leave a newer-looking library built from different code.
"""

from __future__ import annotations

import glob
import hashlib
import os
import subprocess
from typing import List, Optional

from ..obs import spans as _spans

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(_PKG_DIR))
BUILD_DIR = os.path.join(_PKG_DIR, "_build")


def native_source(name: str) -> str:
    return os.path.join(REPO_ROOT, "native", name)


def compiler_candidates() -> List[str]:
    """$CXX first when set, then the conventional fallback chain."""
    env = os.environ.get("CXX", "").strip()
    out = [env] if env else []
    for cxx in ("g++", "clang++", "cc"):
        if cxx not in out:
            out.append(cxx)
    return out


def _compile(src: str, dst: str) -> bool:
    """Try each candidate compiler until one produces ``dst``. ``-x c++``
    + ``-lstdc++`` keep a bare ``cc`` driver viable for the C++ source."""
    for cxx in compiler_candidates():
        # Per-pid temp + atomic replace: concurrent builders (parallel
        # pytest, fleet workers) must never interleave writes into a
        # library another process is loading.
        tmp = f"{dst}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                [cxx, "-O2", "-shared", "-fPIC", "-x", "c++", src,
                 "-o", tmp, "-lstdc++"],
                check=True, capture_output=True, timeout=120,
            )
        except (OSError, subprocess.SubprocessError):
            try:
                os.unlink(tmp)
            except OSError:
                pass
            continue
        os.replace(tmp, dst)
        return True
    return False


def build_library(
    src: str, stem: str, rebuild: bool = False
) -> Optional[str]:
    """Path of the shared library built from ``src`` (compiling it when no
    library for this exact source content exists yet, or when ``rebuild``),
    or None when the source is missing or no compiler links it."""
    with _spans.stage("setup.native_build", stem=stem, compiled=False) as st:
        try:
            with open(src, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()[:16]
        except OSError:
            return None
        so = os.path.join(BUILD_DIR, f"{stem}-{digest}.so")
        if rebuild or not os.path.exists(so):
            os.makedirs(BUILD_DIR, exist_ok=True)
            st.set(compiled=True)
            if not _compile(src, so):
                return None
            # Libraries built from other source contents are dead weight.
            for old in glob.glob(os.path.join(BUILD_DIR, f"{stem}*.so")):
                if old != so:
                    try:
                        os.unlink(old)
                    except OSError:
                        pass
        return so
