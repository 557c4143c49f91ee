"""Shared build/caching for the framework's native C++ components.

One g++ invocation per source file, cached in `runtime/_build/` keyed by
source mtime.  Used by the scoring engine (csrc/shifu_scorer.cc), the
data parser (csrc/shifu_parser.cc) and the eval's accumulation
(csrc/shifu_evalacc.cc); all are dependency-free C ABI shared
libraries bindable from Python (ctypes) and the JVM (JNA/JNI) — the authored
native-code layer replacing the reference's consumed TF C++ runtime
(shifu-tensorflow-eval/pom.xml:59-73).
"""

from __future__ import annotations

import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional, Sequence

_CSRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "csrc")
_BUILD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")
_lock = threading.Lock()


def _machine_tag() -> str:
    """Short id of this host's CPU capabilities.  Builds use -march=native,
    so a cached .so must never be loaded on a CPU with a different ISA (a
    shared filesystem or baked container image would otherwise SIGILL) —
    the tag goes into the library filename."""
    probe = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):  # x86 / arm
                    probe += ":" + line.split(":", 1)[1].strip()
                    break
    except OSError:
        probe += ":" + platform.processor()
    return hashlib.sha1(probe.encode()).hexdigest()[:10]


def _flags_tag(*flag_groups: Sequence[str]) -> str:
    """Short hash of the flag sets baked into a cached artifact, so changing
    link/sanitize flags never reuses an executable built with the old ones."""
    return hashlib.sha1("\x00".join(
        f for g in flag_groups for f in g).encode()).hexdigest()[:8]


def _source_mtime(src: str) -> float:
    """Newest mtime among the source and sibling headers it may include —
    a header-only edit must invalidate the cached artifact too."""
    mtimes = [os.path.getmtime(src)]
    src_dir = os.path.dirname(src)
    for name in os.listdir(src_dir):
        if name.endswith((".h", ".hpp")):
            mtimes.append(os.path.getmtime(os.path.join(src_dir, name)))
    return max(mtimes)


def _compile_cached(
    src: str,
    out_path: str,
    flag_variants: Sequence[Sequence[str]],
    tail: Sequence[str],
    force: bool = False,
) -> str:
    """Shared compile-and-cache: rebuild `out_path` from `src` when missing or
    stale, trying each flag variant in order (first success wins).  `tail` is
    appended after the source (link libraries).  Callers must bake every
    cache-relevant flag into `out_path`'s name (see _flags_tag)."""
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with _lock:
        if (os.path.exists(out_path) and not force
                and os.path.getmtime(out_path) >= _source_mtime(src)):
            return out_path
        # written under a name of this process's own and renamed into place:
        # processes that build the same artifact at once (test workers, a
        # job's hosts on a shared disk) never load a half-written file
        tmp = f"{out_path}.{os.getpid()}.tmp"
        for flags in flag_variants:
            cmd = ["g++", *flags, "-o", tmp, src, *tail]
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode == 0:
                os.replace(tmp, out_path)
                return out_path
        raise RuntimeError(
            f"native build failed ({' '.join(cmd)}):\n{proc.stderr}")


def build_library(
    source_name: str,
    extra_flags: Sequence[str] = (),
    out_dir: Optional[str] = None,
    force: bool = False,
) -> str:
    """Compile `csrc/<source_name>` into a cached .so; returns its path.

    Raises RuntimeError with the compiler's stderr on failure so callers can
    fall back to pure-Python paths with a loggable reason.
    """
    src = os.path.join(_CSRC, source_name)
    out_dir = os.path.abspath(out_dir or _BUILD)
    # libraries are built on (and cached for) the machine that runs them, so
    # tune for it: -march=native unlocks AVX/FMA for the scorer's matmuls and
    # the parser's tokenizer; retry without it for compilers/platforms that
    # reject the flag
    base = ["-O3", "-shared", "-fPIC", "-std=c++17"]
    variants = [["-march=native", "-funroll-loops", *base], base]
    lib_path = os.path.join(
        out_dir, "lib" + os.path.splitext(source_name)[0] + "-"
        + _machine_tag() + "-" + _flags_tag(*variants, extra_flags) + ".so")
    return _compile_cached(src, lib_path, variants, extra_flags, force=force)


def build_selftest(
    source_name: str,
    sanitize: str = "address,undefined",
    extra_flags: Sequence[str] = (),
    out_dir: Optional[str] = None,
    force: bool = False,
) -> str:
    """Compile `csrc/<source_name>` with -DSHIFU_SELFTEST_MAIN into a
    sanitizer-instrumented executable; returns its path.

    This is the framework's memory/UB detection harness — coverage dimension
    the reference had none of (SURVEY.md §5.2).  Run the binary; exit 0 means
    the kernels passed under ASan/UBSan.
    """
    src = os.path.join(_CSRC, source_name)
    out_dir = os.path.abspath(out_dir or _BUILD)
    # -fno-sanitize-recover: UBSan otherwise only *reports* and exits 0,
    # which would let UB through the tests' returncode assertion
    flags = ["-O1", "-g", "-fno-omit-frame-pointer", f"-fsanitize={sanitize}",
             "-fno-sanitize-recover=all", "-DSHIFU_SELFTEST_MAIN",
             "-std=c++17"]
    exe = os.path.join(
        out_dir, os.path.splitext(source_name)[0] + "-selftest-"
        + sanitize.replace(",", "_") + "-" + _flags_tag(flags, extra_flags))
    return _compile_cached(src, exe, [flags], extra_flags, force=force)
