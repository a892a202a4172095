"""Native (C++) components: build + ctypes loading.

The reference builds its C++ kernels with ``g++ -O3`` into a static lib
linked from Go (elasticdl/Makefile:22-24). Here the shared libraries
build lazily on first import, next to their sources, under a file name
that carries a digest of the sources' content: a copy of the tree that
resets mtimes, or an edit that keeps them, can neither serve a stale
binary nor rebuild a fresh one. They bind via ctypes / the extension
loader — pybind11 is not in the image.

A build or load that fails is an error. ELASTICDL_TPU_NO_NATIVE=1 is
the one way onto the pure-Python versions, and it is the caller's
choice, not a silent one.
"""

import contextlib
import ctypes
import glob
import hashlib
import os
import subprocess
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
_SOURCES = [
    os.path.join(_HERE, "row_store.cc"),
]
# The record reader is a CPython extension (record_ext.c): it returns
# list[bytes] built in C, which a ctypes design cannot do without a
# second Python-side pass (measured slower than the pure scanner).
_EXT_SRC = os.path.join(_HERE, "record_ext.c")

_ext = None
_ext_load_attempted = False

_lib = None
_load_attempted = False


def _ensure_built(stem: str, sources, command) -> str:
    """Path of ``<stem>-<digest of sources>.so``, compiled by
    ``command(output_path)`` unless it already exists."""
    digest = hashlib.sha256()
    for src in sources:
        with open(src, "rb") as f:
            digest.update(f.read())
    path = os.path.join(_HERE, f"{stem}-{digest.hexdigest()[:16]}.so")
    if os.path.exists(path):
        return path
    # Compile to a temp file, atomic-rename into place (concurrent
    # importers race benignly).
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        subprocess.run(
            command(tmp), check=True, capture_output=True, timeout=120
        )
        os.replace(tmp, path)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError) as exc:
        detail = getattr(exc, "stderr", b"") or b""
        raise RuntimeError(
            f"native build of {stem} failed ({exc}) {detail.decode()}"
            " — set ELASTICDL_TPU_NO_NATIVE=1 to run on the "
            "pure-Python versions"
        ) from exc
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    for old in glob.glob(os.path.join(_HERE, f"{stem}-*.so")):
        if old != path:
            with contextlib.suppress(FileNotFoundError):
                os.unlink(old)  # a concurrent importer got there first
    return path


def _bind(lib):
    c = ctypes
    i64, u32, f32 = c.c_int64, c.c_uint32, c.c_float
    p, i64p, f32p = c.c_void_p, c.POINTER(c.c_int64), c.POINTER(c.c_float)
    lib.rs_create.restype = p
    lib.rs_create.argtypes = [i64, u32, c.c_int, f32, f32]
    lib.rs_destroy.argtypes = [p]
    lib.rs_num_rows.restype = i64
    lib.rs_num_rows.argtypes = [p]
    lib.rs_dim.restype = i64
    lib.rs_dim.argtypes = [p]
    lib.rs_created_count.restype = i64
    lib.rs_created_count.argtypes = [p]
    lib.rs_erase.restype = i64
    lib.rs_erase.argtypes = [p, i64p, i64]
    lib.rs_contains.argtypes = [p, i64p, i64,
                                c.POINTER(c.c_uint8)]
    lib.rs_get.argtypes = [p, i64p, i64, f32p]
    lib.rs_set.argtypes = [p, i64p, i64, f32p]
    lib.rs_export.argtypes = [p, i64p, f32p]
    lib.rs_sgd.argtypes = [p, i64p, i64, f32p, f32]
    lib.rs_momentum.argtypes = [p, p, i64p, i64, f32p, f32, f32, c.c_int]
    lib.rs_adagrad.argtypes = [p, p, i64p, i64, f32p, f32, f32]
    lib.rs_adam.argtypes = [p, p, p, p, i64p, i64, f32p, f32, f32, f32,
                            f32, i64]
    return lib


def get_lib():
    """The loaded row-store library; None only under
    ELASTICDL_TPU_NO_NATIVE."""
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    if not os.environ.get("ELASTICDL_TPU_NO_NATIVE"):
        path = _ensure_built(
            "_librowstore", _SOURCES,
            lambda out: ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                         "-o", out, *_SOURCES],
        )
        _lib = _bind(ctypes.CDLL(path))
    _load_attempted = True
    return _lib


def native_available() -> bool:
    return get_lib() is not None


def get_record_ext():
    """The _record_ext extension module; None only under
    ELASTICDL_TPU_NO_NATIVE."""
    global _ext, _ext_load_attempted
    if _ext_load_attempted:
        return _ext
    if os.environ.get("ELASTICDL_TPU_NO_NATIVE"):
        _ext_load_attempted = True
        return None
    import importlib.machinery
    import importlib.util
    import sysconfig

    include = sysconfig.get_paths()["include"]
    path = _ensure_built(
        "_record_ext", [_EXT_SRC],
        lambda out: ["gcc", "-O3", "-shared", "-fPIC", f"-I{include}",
                     "-o", out, _EXT_SRC],
    )
    # The name must match the C module's PyInit__record_ext.
    loader = importlib.machinery.ExtensionFileLoader("_record_ext", path)
    spec = importlib.util.spec_from_loader("_record_ext", loader)
    module = importlib.util.module_from_spec(spec)
    loader.exec_module(module)
    _ext = module
    _ext_load_attempted = True
    return _ext
