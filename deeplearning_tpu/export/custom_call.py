"""XLA FFI custom-call registration demo.

The "teach the compiler a new op" tutorial the reference does for ONNX
(others/deploy/pytorch2onnx: my_add.cpp + setup.py + support_new_ops.py
g.op symbolic). TPU-era flow: C++ handler built against jaxlib's FFI
headers (native/my_add.cc), registered for the Host platform, invoked
via jax.ffi.ffi_call — usable under jit and composable with everything
else (CPU callback path; a real TPU kernel would be Pallas instead, see
ops/pallas/).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LOCK = threading.Lock()
_REGISTERED = False


def _build() -> Optional[str]:
    src = os.path.join(_DIR, "my_add.cc")
    out = os.path.join(_DIR, "libmy_add.so")
    if os.path.exists(out) and \
            os.path.getmtime(out) >= os.path.getmtime(src):
        return out
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
           f"-I{jax.ffi.include_dir()}", src, "-o", out]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        return out
    except (subprocess.CalledProcessError, FileNotFoundError,
            subprocess.TimeoutExpired):
        return None


def register() -> bool:
    """Compile + register the MyAdd FFI handler (idempotent). Returns
    False when no host compiler is available."""
    global _REGISTERED
    with _LOCK:
        if _REGISTERED:
            return True
        path = _build()
        if path is None:
            return False
        lib = ctypes.CDLL(path)
        jax.ffi.register_ffi_target(
            "my_add", jax.ffi.pycapsule(lib.MyAdd), platform="cpu")
        _REGISTERED = True
        return True


def my_add(a: jax.Array, b: jax.Array) -> jax.Array:
    """3a + 2b via the native handler (my_add.cpp semantics)."""
    if not register():
        raise RuntimeError("no host toolchain to build the FFI demo")
    call = jax.ffi.ffi_call(
        "my_add", jax.ShapeDtypeStruct(a.shape, jnp.float32))
    return call(a.astype(jnp.float32), b.astype(jnp.float32))
