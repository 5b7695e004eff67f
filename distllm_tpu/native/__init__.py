"""Native (C++) runtime components, built on demand with the system toolchain.

The reference's native substrate lives in its dependencies (vLLM's C++ block
manager, FAISS, etc. — SURVEY.md section 2.4); the equivalents here are
first-party C++ compiled into small shared objects and loaded via ctypes.
A pure-Python fallback exists for every component so the framework still
works where no compiler is available.
"""

from __future__ import annotations

import hashlib
import subprocess
from pathlib import Path

_NATIVE_DIR = Path(__file__).parent
_BUILD_DIR = _NATIVE_DIR / '_build'


def build_library(source_name: str) -> Path | None:
    """Compile ``source_name`` (e.g. ``scheduler.cpp``) to a cached .so.

    Returns the .so path, or None when compilation is unavailable/fails.
    The cache key includes the source hash so edits rebuild automatically.
    """
    source = _NATIVE_DIR / source_name
    digest = hashlib.sha256(source.read_bytes()).hexdigest()[:16]
    so_path = _BUILD_DIR / f'{source.stem}-{digest}.so'
    if so_path.exists():
        return so_path
    _BUILD_DIR.mkdir(exist_ok=True)
    try:
        subprocess.run(
            [
                'g++', '-O2', '-shared', '-fPIC', '-std=c++17',
                str(source), '-o', str(so_path),
            ],
            check=True,
            capture_output=True,
            timeout=120,
        )
        return so_path
    except (subprocess.CalledProcessError, FileNotFoundError, subprocess.TimeoutExpired):
        return None
