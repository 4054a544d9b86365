"""ctypes surface for the native C++ PJRT client.

The reference's INDArray math enters native code through ND4J's backends
(SURVEY.md §2.9); our native tensor-runtime boundary is
``native/pjrt_client.cpp`` — a C++ PJRT client that dlopens any XLA
backend plugin (the TPU plugin included), compiles StableHLO/VHLO, and
executes on device buffers without Python in the loop. This module is
the thin ctypes veneer plus helpers to (a) serialize a jax function to
the portable VHLO + CompileOptions pair the client consumes and (b)
locate the installed TPU plug-in.

JAX remains the production compute path; this proves and exercises the
§7-stage-1 native layer end to end.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional, Sequence, Tuple

import numpy as np

from deeplearning4j_tpu.native_rt.lib import _NATIVE_DIR

_PJRT_SO = os.path.join(_NATIVE_DIR, "libdl4j_pjrt.so")


def _pjrt_headers() -> Optional[str]:
    """Locate the PJRT C API headers from the running environment."""
    try:
        import numpy
    except ImportError:
        return None
    site = os.path.dirname(os.path.dirname(numpy.__file__))
    cand = os.path.join(site, "tensorflow", "include")
    header = os.path.join(cand, "tensorflow", "compiler", "xla", "pjrt",
                          "c", "pjrt_c_api.h")
    return cand if os.path.exists(header) else None


def _build_if_needed() -> bool:
    if os.path.exists(_PJRT_SO):
        return True
    src = os.path.join(_NATIVE_DIR, "pjrt_client.cpp")
    headers = _pjrt_headers()
    if not os.path.exists(src) or headers is None:
        return False
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, "pjrt",
             f"PJRT_INCLUDE={headers}"],
            check=True, capture_output=True, timeout=180)
    except (OSError, subprocess.SubprocessError):
        return False
    return os.path.exists(_PJRT_SO)


class PjrtClient:
    """Own a native PJRT client over a plugin .so."""

    def __init__(self, plugin_path: str, options: str = ""):
        if not _build_if_needed():
            raise RuntimeError("libdl4j_pjrt.so unavailable (no headers "
                               "or toolchain to build it)")
        lib = self._lib = ctypes.CDLL(_PJRT_SO)
        lib.dl4j_pjrt_open.restype = ctypes.c_void_p
        lib.dl4j_pjrt_open.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_int]
        lib.dl4j_pjrt_close.argtypes = [ctypes.c_void_p]
        lib.dl4j_pjrt_device_count.argtypes = [ctypes.c_void_p]
        lib.dl4j_pjrt_platform.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
        lib.dl4j_pjrt_run_f32.restype = ctypes.c_int64
        lib.dl4j_pjrt_run_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32, ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int]
        # serving API (compile-once, multi-arg execute, device buffers)
        lib.dl4j_pjrt_compile.restype = ctypes.c_void_p
        lib.dl4j_pjrt_compile.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_int]
        lib.dl4j_pjrt_exe_destroy.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p]
        lib.dl4j_pjrt_buffer_from_host_f32.restype = ctypes.c_void_p
        lib.dl4j_pjrt_buffer_from_host_f32.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_int]
        lib.dl4j_pjrt_buffer_destroy.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p]
        lib.dl4j_pjrt_buffer_to_host_f32.restype = ctypes.c_int64
        lib.dl4j_pjrt_buffer_to_host_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_int]
        lib.dl4j_pjrt_execute.restype = ctypes.c_int64
        lib.dl4j_pjrt_execute.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int32,
            ctypes.c_char_p, ctypes.c_int]
        err = ctypes.create_string_buffer(4096)
        self._h = lib.dl4j_pjrt_open(
            plugin_path.encode(), options.encode(), err, len(err))
        if not self._h:
            raise RuntimeError(
                f"PJRT client create failed: {err.value.decode(errors='replace')}")

    def device_count(self) -> int:
        return self._lib.dl4j_pjrt_device_count(self._h)

    def platform(self) -> str:
        buf = ctypes.create_string_buffer(64)
        self._lib.dl4j_pjrt_platform(self._h, buf, len(buf))
        return buf.value.decode()

    def run_f32(self, code: bytes, x: np.ndarray,
                compile_options: bytes = b"",
                out_capacity: int = 1 << 20) -> np.ndarray:
        """Compile + execute a 1-input/1-output f32 program; returns the
        flat output floats."""
        x = np.ascontiguousarray(x, np.float32)
        dims = (ctypes.c_int64 * x.ndim)(*x.shape)
        out = (ctypes.c_float * out_capacity)()
        err = ctypes.create_string_buffer(4096)
        n = self._lib.dl4j_pjrt_run_f32(
            self._h, code, len(code), compile_options,
            len(compile_options),
            x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            dims, x.ndim, out, out_capacity, err, len(err))
        if n < 0:
            raise RuntimeError(
                f"PJRT run failed: {err.value.decode(errors='replace')[:500]}")
        return np.ctypeslib.as_array(out)[:n].copy()

    def close(self) -> None:
        if self._h:
            self._lib.dl4j_pjrt_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def serialize_for_pjrt(fn, *example_args) -> Tuple[bytes, bytes]:
    """(VHLO bytecode, serialized CompileOptionsProto) for a jittable
    function — the portable pair PjrtClient.run_f32 /
    CompiledProgram consume."""
    import jax

    from jax import export as jax_export

    exported = jax_export.export(jax.jit(fn))(*example_args)
    from jax._src import compiler

    copts = compiler.get_compile_options(
        num_replicas=1, num_partitions=1).SerializeAsString()
    return exported.mlir_module_serialized, copts


def export_network_for_native(net, example_input) -> Tuple[bytes, bytes]:
    """Serialize a trained MultiLayerNetwork/ComputationGraph forward
    pass (params baked in as constants) to the (VHLO, CompileOptions)
    pair — deploy-time serving through the C++ client with no Python or
    jax process on the box."""
    import jax
    import jax.numpy as jnp

    params = jax.tree.map(jnp.asarray, net.params)
    state = jax.tree.map(jnp.asarray, net.state) if net.state else {}
    is_graph = hasattr(net.conf, "network_inputs")
    if is_graph and (len(net.conf.network_inputs) != 1
                     or len(net.conf.network_outputs) != 1):
        raise ValueError(
            "export_network_for_native serves single-input/single-output "
            f"models; graph has {len(net.conf.network_inputs)} inputs / "
            f"{len(net.conf.network_outputs)} outputs")

    def forward(x):
        if is_graph:
            acts = net._forward_fn(
                params, state, {net.conf.network_inputs[0]: x}, None,
                False)[0]
            out = acts[net.conf.network_outputs[0]]
        else:
            out = net._forward_fn(params, state, x, None, False)[0]
        # the C ABI moves f32 bytes; a compute_dtype="bfloat16" net would
        # otherwise export a bf16 result the client misreads
        return out.astype(jnp.float32)

    # Serve at full precision: the TPU's default bf16 matmul passes are
    # a training trade-off; exported inference should match the trained
    # model's f32 outputs.
    with jax.default_matmul_precision("highest"):
        return serialize_for_pjrt(forward, jnp.asarray(example_input))


class DeviceBuffer:
    """A device-resident PJRT buffer owned by the native client (the
    decode loop's cache tensors never round-trip to host)."""

    def __init__(self, client: "PjrtClient", handle):
        self._client = client
        self._h = handle

    def to_host(self, capacity: int = 1 << 20) -> np.ndarray:
        lib, h = self._client._lib, self._client._h
        # np.empty, not a ctypes array: ctypes zero-fills its buffer,
        # which costs milliseconds at MB sizes — inside the per-token
        # decode loop that allocator noise would pollute the latency
        # this API exists to measure.
        out = np.empty(capacity, np.float32)
        err = ctypes.create_string_buffer(4096)
        n = lib.dl4j_pjrt_buffer_to_host_f32(
            h, self._h,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            capacity, err, len(err))
        if n < 0:
            raise RuntimeError(
                f"buffer fetch failed: "
                f"{err.value.decode(errors='replace')[:300]}")
        return out[:n].copy()

    def destroy(self) -> None:
        if self._h:
            self._client._lib.dl4j_pjrt_buffer_destroy(
                self._client._h, self._h)
            self._h = None


class CompiledProgram:
    """A compile-ONCE executable on the native client: ``execute``
    takes/returns DeviceBuffers (N args, M outputs) — the serving-loop
    shape (per-step recompilation or host round-trips of the KV cache
    would dominate decode latency)."""

    def __init__(self, client: "PjrtClient", code: bytes,
                 compile_options: bytes = b""):
        self._client = client
        err = ctypes.create_string_buffer(4096)
        lib = client._lib
        self._h = lib.dl4j_pjrt_compile(
            client._h, code, len(code), compile_options,
            len(compile_options), err, len(err))
        if not self._h:
            raise RuntimeError(
                f"PJRT compile failed: "
                f"{err.value.decode(errors='replace')[:500]}")

    def execute(self, inputs, max_outputs: int = 256):
        """inputs: list of DeviceBuffer; returns list of DeviceBuffer."""
        lib, h = self._client._lib, self._client._h
        n_in = len(inputs)
        in_arr = (ctypes.c_void_p * n_in)(
            *[b._h for b in inputs])
        out_arr = (ctypes.c_void_p * max_outputs)()
        err = ctypes.create_string_buffer(4096)
        n = lib.dl4j_pjrt_execute(
            h, self._h, in_arr, n_in, out_arr, max_outputs, err,
            len(err))
        if n < 0:
            raise RuntimeError(
                f"PJRT execute failed: "
                f"{err.value.decode(errors='replace')[:500]}")
        return [DeviceBuffer(self._client, out_arr[i])
                for i in range(n)]

    def destroy(self) -> None:
        if self._h:
            self._client._lib.dl4j_pjrt_exe_destroy(
                self._client._h, self._h)
            self._h = None


def buffer_from_host(client: "PjrtClient", x: np.ndarray) -> DeviceBuffer:
    x = np.ascontiguousarray(x, np.float32)
    dims = (ctypes.c_int64 * x.ndim)(*x.shape)
    err = ctypes.create_string_buffer(4096)
    h = client._lib.dl4j_pjrt_buffer_from_host_f32(
        client._h, x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        dims, x.ndim, err, len(err))
    if not h:
        raise RuntimeError(
            f"buffer upload failed: "
            f"{err.value.decode(errors='replace')[:300]}")
    return DeviceBuffer(client, h)


def export_decode_step_for_native(net, n_batch: int = 1):
    """Serialize ONE KV-cache decode step of a causal attention net
    (params baked in) to the (VHLO, CompileOptions) pair plus the cache
    template the caller zero-initializes.

    The exported function is
    ``(x_t [B, C, 1], *cache_leaves_f32) -> (logits [B, V, 1],
    *new_cache_leaves_f32)`` with FIXED shapes (attention.py
    stream_max_t sliding cache — one compiled step serves any context
    length). int32 cache leaves (the 'filled' counters) ride as f32
    through the C ABI and are cast back inside the program.

    Returns (code, copts, cache_template, treedef) where
    cache_template is a list of zero np.float32 arrays in flatten
    order."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.attention import guard_streamable

    guard_streamable(
        (str(i), c.layer) for i, c in enumerate(net.conf.confs))
    params = jax.tree.map(jnp.asarray, net.params)
    state = jax.tree.map(jnp.asarray, net.state) if net.state else {}
    n_in = net.conf.confs[0].layer.n_in

    # Probe the cache structure: one streaming step from empty state.
    x_probe = jnp.zeros((n_batch, n_in, 1), jnp.float32)
    _, _, rnn0 = jax.eval_shape(
        lambda x: net._forward_fn(params, state, x, None, False,
                                  rnn_state=None), x_probe)
    leaves, treedef = jax.tree.flatten(rnn0)
    dtypes = [l.dtype for l in leaves]
    template = [np.zeros(l.shape, np.float32) for l in leaves]

    def decode_step(x, *cache_f32):
        cache = jax.tree.unflatten(
            treedef,
            [c.astype(d) for c, d in zip(cache_f32, dtypes)])
        out, _, new_rnn = net._forward_fn(
            params, state, x, None, False, rnn_state=cache)
        new_flat = [l.astype(jnp.float32)
                    for l in jax.tree.leaves(new_rnn)]
        return (out.astype(jnp.float32), *new_flat)

    with jax.default_matmul_precision("highest"):
        code, copts = serialize_for_pjrt(
            decode_step, x_probe, *[jnp.asarray(t) for t in template])
    return (code, copts, template, treedef)


def tpu_plugin_path() -> Optional[str]:
    """The installed TPU PJRT plug-in (libtpu's shared library) when
    this host has a TPU chip, else None. The plug-in takes no option
    string: ``PjrtClient(tpu_plugin_path(), "")``."""
    from deeplearning4j_tpu.util.chips import local_tpu_chips

    if not local_tpu_chips():
        return None
    import libtpu

    return libtpu.get_library_path()
