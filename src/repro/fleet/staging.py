"""One host-to-device transfer for a chunk's inputs.

A new chunk needs about twenty small arrays on the device (the
`FleetState` fields, the per-row tables and masks, the settings
scalars).  One `jax.device_put` each costs a fixed host latency per
array that dwarfs their bytes at paper scale.  `pack` lays them out back
to back in one uint32 host buffer, and `stage` splits that buffer on the
device in one jitted call, so a chunk costs one transfer:

  * 32-bit arrays (int32, float32) keep their bits, one word per element,
    and come back through `lax.bitcast_convert_type`;
  * booleans go one bit per element, eight times smaller than a bool
    array, in 32 bit planes: bit j of word w holds element j * W + w of
    the flattened array, for a segment of W words.  Unpacking then
    interleaves no bits within a word.

Each array's segment starts on a multiple of `_ALIGN` words; the static
``spec`` (each array's shape and dtype) gives the offsets.  Interleaved
bits, or segments at unaligned offsets, take the TPU's compiler seconds
to tens of seconds at catalog sizes (n = 129,024), against about one
second this way.  The round trip is exact: the staged arrays equal the
host arrays in dtype, shape and bits.

`stage` also builds the chunk's per-row geometry from device-resident
per-space arrays (the session keeps one per space and device), so the
(rows, n, d) encoding never crosses from the host.
"""

from __future__ import annotations

import math
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["pack", "stage"]

Spec = Tuple[Tuple[Tuple[int, ...], np.dtype], ...]

_ALIGN = 128  # words: the TPU's lane width
_BITS = np.arange(8, dtype=np.uint8)


def _words(shape: Tuple[int, ...], dtype: np.dtype) -> int:
    """uint32 words of one array's segment."""
    count = math.prod(shape)
    if dtype == np.bool_:
        count = -(-count // 32)
    elif dtype.itemsize != 4:
        raise ValueError(f"cannot stage {dtype} arrays: 32-bit or bool only")
    return -(-count // _ALIGN) * _ALIGN


def pack(arrays: Sequence) -> Tuple[np.ndarray, Spec]:
    """``arrays`` in one uint32 buffer, and the spec `stage` reads by."""
    arrays = [np.asarray(a) for a in arrays]
    spec = tuple((a.shape, a.dtype) for a in arrays)
    sizes = [_words(a.shape, a.dtype) for a in arrays]
    buf = np.zeros(sum(sizes), np.uint32)
    off = 0
    for a, w in zip(arrays, sizes):
        if a.dtype == np.bool_:
            planes = np.zeros((4, 8, w), np.uint8)
            planes.reshape(-1)[: a.size] = a.reshape(-1)
            # Byte k of word w: planes 8k..8k+7, plane 8k + b at bit b.
            octets = np.bitwise_or.reduce(planes << _BITS[:, None], axis=1)
            buf[off : off + w] = np.ascontiguousarray(octets.T).view("<u4")[:, 0]
        else:
            buf[off : off + a.size] = a.reshape(-1).view(np.uint32)
        off += w
    return buf, spec


def _unpack(buf: jax.Array, spec: Spec) -> List[jax.Array]:
    out, off = [], 0
    for shape, dtype in spec:
        count = math.prod(shape)
        w = _words(shape, dtype)
        words = buf[off : off + w]
        if dtype == np.bool_:
            planes = jnp.arange(32, dtype=jnp.uint32)[:, None]
            bits = (words[None, :] >> planes) & 1
            x = bits.reshape(-1)[:count].astype(bool)
        else:
            x = lax.bitcast_convert_type(words[:count], dtype)
        out.append(x.reshape(shape))
        off += w
    return out


@partial(jax.jit, static_argnames=("spec",))
def stage(buf: jax.Array, geoms: Tuple[jax.Array, ...], *, spec: Spec):
    """Split a `pack` buffer on the device, and stack the chunk's geometry.

    ``geoms`` holds one array per chunk row (rows of one space share
    one).  The buffer's last array is an int32 scalar, the number of
    member rows: the rows after them are dummies, and their geometry is
    zero.  Returns (geometry, the other arrays in order).  Its static
    signature is the chunk's shapes, so it compiles where the chunk's
    update does."""
    *arrays, members = _unpack(buf, spec)
    stacked = jnp.stack(geoms)
    live = jnp.arange(len(geoms)) < members
    live = live.reshape(live.shape + (1,) * (stacked.ndim - 1))
    geom = jnp.where(live, stacked, jnp.zeros((), stacked.dtype))
    return geom, arrays
