"""Host-side sub-byte packing for the tuGEMM packed kernels.

Plane layout (not nibble-interleaved): for int4, ``packed[k, n]`` holds
``W[k, n]`` in bits 0-3 and ``W[k + K/2, n]`` in bits 4-7. GEMM accumulation
is order-independent over K, so the kernel computes
``A[:, :K/2] @ low + A[:, K/2:] @ high`` — every unpacked plane feeds the MXU
directly with no in-VMEM interleave (DESIGN.md §2A: the TPU embodiment of
"fewer bits ⇒ proportionally less hardware" is proportionally less HBM
traffic).
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["BITS_TO_PLANES", "pack_planes", "unpack_plane", "pad_to_multiple"]

# sub-byte plane counts — the single source of truth for the bits→planes
# map (ops/tugemm_fused/ref extend it with the trivial 8-bit entry)
BITS_TO_PLANES = {4: 2, 2: 4}


def pad_to_multiple(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pack_planes(w: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Pack int values (|w| < 2**(bits-1) two's complement) along axis 0.

    w: (K, N) int8 with K a multiple of the plane count. Returns
    (K/planes, N) int8 where plane ``p`` of row k holds ``w[k + p*K/planes, n]``
    in bit positions ``[p*bits, (p+1)*bits)``.
    """
    planes = BITS_TO_PLANES[bits]
    K = w.shape[0]
    if K % planes:
        raise ValueError(f"K={K} must be a multiple of {planes} for {bits}-bit packing")
    kp = K // planes
    w8 = w.astype(jnp.int8)
    mask = (1 << bits) - 1
    out = jnp.zeros((kp, *w.shape[1:]), dtype=jnp.uint8)
    for p in range(planes):
        plane = (w8[p * kp : (p + 1) * kp].astype(jnp.uint8) & mask).astype(jnp.uint8)
        out = out | (plane << (p * bits))
    return out.astype(jnp.int8)


def unpack_plane(packed: jnp.ndarray, bits: int, plane: int) -> jnp.ndarray:
    """Extract plane ``plane`` as sign-extended int8 (works inside Pallas)."""
    planes = BITS_TO_PLANES[bits]
    if not 0 <= plane < planes:
        raise ValueError(f"plane {plane} out of range for {bits}-bit")
    shift_up = 8 - (plane + 1) * bits
    # shift in int32 (Mosaic has no int8 shifts): move the plane to the top
    # byte, then the arithmetic right shift sign-extends it
    p = packed.astype(jnp.int8).astype(jnp.int32)
    return ((p << (24 + shift_up)) >> (32 - bits)).astype(jnp.int8)
