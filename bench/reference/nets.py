"""Plain forward passes of the proxy and the tracker's crop network, and
the convolution every reference network (the detector families' too,
``bench/reference/detectors``) is built from, in JAX at full precision.

Straight from the architecture the configuration file states: strided
3x3 convolutions with 'SAME' padding and ReLU, a 1x1 head, sigmoid
scores.  Nothing of the program is imported; the weights come from the
benchmark's model cache as flat ``"<model>/<scope>/<leaf>"`` arrays.

Every convolution and contraction runs at ``Precision.HIGHEST``.  With
``operands`` set (a dtype name), each operand is first rounded to that
dtype and back, which computes the same network with lower-precision
operands and float32 accumulation: the control of the comparison.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def rounded(x, operands: Optional[str]):
    if operands is None:
        return x
    return x.astype(jnp.dtype(operands)).astype(jnp.float32)


def conv(x, w, b, stride: int, operands: Optional[str]):
    y = jax.lax.conv_general_dilated(
        rounded(x, operands), rounded(w, operands), (stride, stride),
        "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)
    return y + b


def take(weights: Dict[str, np.ndarray], prefix: str) -> Dict[str, jnp.ndarray]:
    n = len(prefix) + 1
    return {k[n:]: jnp.asarray(v, jnp.float32) for k, v in weights.items()
            if k.startswith(prefix + "/")}


@functools.partial(jax.jit, static_argnames=("levels", "operands"))
def proxy(p, frames, levels: int, operands: Optional[str] = None):
    """frames (B, h, w, 3) -> cell scores (B, h/C, w/C), C = 2**levels."""
    x = frames
    for i in range(levels):
        x = jax.nn.relu(conv(x, p[f"enc{i}/w"], p[f"enc{i}/b"], 2,
                             operands))
    x = jax.nn.relu(conv(x, p["dec0/w"], p["dec0/b"], 1, operands))
    logits = jnp.einsum("bhwc,c->bhw", rounded(x, operands),
                        rounded(p["head/w"], operands),
                        precision=HIGHEST) + p["head/b"][0]
    return jax.nn.sigmoid(logits)


@functools.partial(jax.jit, static_argnames=("operands",))
def crop_cnn(p, crops, operands: Optional[str] = None):
    """crops (N, C, C, 3) -> (N, e) crop features."""
    x = jax.nn.relu(conv(crops, p["crop_cnn/w0"], p["crop_cnn/b0"], 2,
                         operands))
    x = jax.nn.relu(conv(x, p["crop_cnn/w1"], p["crop_cnn/b1"], 2,
                         operands))
    x = x.reshape(x.shape[0], -1)
    return jnp.tanh(jnp.matmul(rounded(x, operands),
                               rounded(p["crop_cnn/wd"], operands),
                               precision=HIGHEST) + p["crop_cnn/bd"])


def batched(fn, arrays: np.ndarray, batch: int, **kw):
    """Run ``fn`` over ``arrays`` in fixed-size batches (one compiled
    shape), returning numpy outputs for the real rows."""
    n = len(arrays)
    outs = []
    for s in range(0, n, batch):
        part = arrays[s:s + batch]
        pad = np.zeros((batch,) + part.shape[1:], np.float32)
        pad[:len(part)] = part
        res = fn(jnp.asarray(pad), **kw)
        if isinstance(res, tuple):
            outs.append(tuple(np.asarray(r)[:len(part)] for r in res))
        else:
            outs.append(np.asarray(res)[:len(part)])
    if not outs:
        return None
    if isinstance(outs[0], tuple):
        return tuple(np.concatenate([o[i] for o in outs])
                     for i in range(len(outs[0])))
    return np.concatenate(outs)
