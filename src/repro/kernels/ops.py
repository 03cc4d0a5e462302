"""Jit'd public wrappers for the Pallas kernels.

Every op takes ``impl=`` with three values:

- ``"pallas"``     — the Pallas kernel: compiled on a TPU, interpreted
                     elsewhere (the registered ``pallas`` backend's
                     ``resolve_interpret(None)``, the same decision the
                     dataflow path makes)
- ``"ref"``        — the pure-jnp oracle from :mod:`repro.kernels.ref`
- ``"auto"``       — pallas on TPU, ref elsewhere (the dry-run path:
                     the XLA lowering is structurally equivalent and
                     keeps compiled HLO analyzable on CPU)

Models call only these wrappers, so kernel selection is a config knob,
never a code change — the FLOWER single-source promise.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.backends import resolve as _resolve
from repro.backends import use_pallas_kernels as _use_pallas
from repro.kernels import ref as _ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.fused_mlp import fused_mlp as _mlp_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas

__all__ = ["attention", "decode_attention", "mlp", "ssd", "rmsnorm"]

# impl= resolution ("pallas" | "ref" | "auto") lives in the backend
# registry (repro.backends.use_pallas_kernels): "auto" asks whether the
# registered pallas backend is native on this platform — the same
# device probe the dataflow stack uses, instead of a local copy.


def _interpret() -> bool:
    return _resolve("pallas").resolve_interpret(None)


def rmsnorm(x, w, eps: float = 1e-6):
    return _ref.rmsnorm_ref(x, w, eps)


def attention(q, k, v, bias=None, causal=True, impl: str = "auto",
              block_q: int = 128, block_k: int = 128, scale=None):
    """q: (B, Hq, Sq, Dk); k: (B, Hkv, Sk, Dk); v: (B, Hkv, Sk, Dv)."""
    if _use_pallas(impl):
        return _flash_pallas(q, k, v, bias=bias, causal=causal,
                             block_q=block_q, block_k=block_k, scale=scale,
                             interpret=_interpret())
    return _ref.flash_attention_ref(q, k, v, bias=bias, causal=causal,
                                    scale=scale)


def decode_attention(q, k, v, bias=None, impl: str = "auto",
                     block_k: int = 512, scale=None):
    """q: (B, Hq, Dk); k: (B, Hkv, S, Dk); v: (B, Hkv, S, Dv)."""
    if _use_pallas(impl):
        return _decode_pallas(q, k, v, bias=bias, block_k=block_k,
                              scale=scale, interpret=_interpret())
    return _ref.decode_attention_ref(q, k, v, bias=bias, scale=scale)


def mlp(x, w_norm, w_gate, w_up, w_down, eps: float = 1e-6,
        impl: str = "auto", block_t: int = 256, block_f: int = 512):
    """Fused rmsnorm+SwiGLU.  x: (..., d) (leading dims flattened)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if _use_pallas(impl):
        y = _mlp_pallas(x2, w_norm, w_gate, w_up, w_down, eps=eps,
                        block_t=block_t, block_f=block_f,
                        interpret=_interpret())
    else:
        y = _ref.fused_mlp_ref(x2, w_norm, w_gate, w_up, w_down, eps=eps)
    return y.reshape(*lead, x.shape[-1])


def ssd(x, dt, A, B, C, chunk: int = 64, impl: str = "auto",
        init_state=None):
    """Mamba2 SSD scan; see ref.ssd_scan_ref for the contract.

    Sequences are padded up to a chunk multiple with dt=0 steps (decay
    exp(0)=1, zero input) — a no-op on both outputs and final state.
    """
    s = x.shape[1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if _use_pallas(impl):
        if init_state is not None:  # kernel starts from zero state
            raise NotImplementedError(
                "pallas ssd_scan does not take init_state; use impl='ref' "
                "for continuation (decode prefill hand-off)")
        y, fs = _ssd_pallas(x, dt, A, B, C, chunk=chunk,
                            interpret=_interpret())
    else:
        y, fs = _ref.ssd_scan_ref(x, dt, A, B, C, chunk=chunk,
                                  init_state=init_state)
    return (y[:, :s] if pad else y), fs
