"""Build the repo's small C++ helpers (csrc/) on first use.

The shared object is keyed on the CONTENT of its source: it is written next
to the source as ``lib<name>.<sha>.so`` (git-ignored), so a binary can only
ever be loaded for the source it was built from. Modification times are not
consulted — a copy of the tree (a checkout, the chip machine) does not keep
them, and a stale binary trusted by mtime would run code nobody committed.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
from typing import Sequence


def build_shared_lib(src: str, name: str, flags: Sequence[str] = ()) -> str:
    """Compile ``src`` with g++ into ``lib<name>.<sha>.so`` beside it (once
    per source content) and return the path. Raises if the build fails."""
    src = os.path.abspath(src)
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(os.path.dirname(src), f"lib{name}.{digest}.so")
    if os.path.exists(out):
        return out
    # build under a private name, then publish atomically: concurrent
    # first users (test workers) never see a half-written object
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", *flags, src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return out
