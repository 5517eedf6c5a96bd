"""Standard layers, NHWC, MXU-friendly.

Covers the layer surface the reference's scripts use — Conv2D / Flatten /
Dense with relu (/root/reference/README.md:58-68, 292-298) — plus the layers
the wider model zoo (ResNet-50, Transformer) needs.

TPU notes:
- Convs/matmuls go through ``lax.conv_general_dilated`` / ``jnp.dot`` so XLA
  tiles them onto the MXU; ``dtype`` selects the compute precision (bfloat16
  recommended) while parameters stay float32.
- All layers are shape-static and trace-free of Python control flow, so the
  whole model jits into one XLA program.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import activations, initializers
from .core import Layer, Shape, child_scope
from ..precision import resolve_dtype
from ..quant import is_quantized_leaf, maybe_dequantize

IntOr2 = Union[int, Tuple[int, int]]


def _pair(v: IntOr2) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def _conv_out(size: int, k: int, s: int, padding: str) -> int:
    if padding.upper() == "SAME":
        return -(-size // s)
    return (size - k) // s + 1


class Conv2D(Layer):
    """2-D convolution over NHWC inputs (kernel laid out HWIO for XLA)."""

    # Convolution mixes neighbouring positions, so the inherited one-token
    # decode would be silently wrong for a sequence model that routes time
    # through a spatial axis; fail loudly instead.
    decode_safe = False

    def __init__(
        self,
        filters: int,
        kernel_size: IntOr2,
        strides: IntOr2 = 1,
        padding: str = "valid",
        activation=None,
        use_bias: bool = True,
        kernel_initializer="glorot_uniform",
        dtype=None,
        name: Optional[str] = None,
    ):
        super().__init__(name)
        self.filters = int(filters)
        self.kernel_size = _pair(kernel_size)
        self.strides = _pair(strides)
        self.padding = padding.upper()
        self.activation = activations.get(activation)
        self.use_bias = use_bias
        self.kernel_initializer = kernel_initializer
        self.dtype = dtype

    def init(self, key, input_shape: Shape):
        h, w, cin = input_shape
        kh, kw = self.kernel_size
        kernel = initializers.get(self.kernel_initializer)(
            key, (kh, kw, cin, self.filters), jnp.float32
        )
        params = {"kernel": kernel}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.filters,), jnp.float32)
        out = (
            _conv_out(h, kh, self.strides[0], self.padding),
            _conv_out(w, kw, self.strides[1], self.padding),
            self.filters,
        )
        return params, {}, out

    def apply(self, params, state, x, *, train=False, rng=None):
        # Weight-only int8 (quant.py): dequantize in-trace, then the
        # layer's own dtype handling applies as if the kernel were f32.
        kernel = maybe_dequantize(params["kernel"])
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
            kernel = kernel.astype(dt)
        y = lax.conv_general_dilated(
            x,
            kernel,
            window_strides=self.strides,
            padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return self.activation(y), {}


class Dense(Layer):
    """Affine map on the trailing axis; works for (B, D) and (B, T, D) alike."""

    def __init__(
        self,
        units: int,
        activation=None,
        use_bias: bool = True,
        kernel_initializer="glorot_uniform",
        dtype=None,
        shard: Optional[str] = None,
        name: Optional[str] = None,
    ):
        super().__init__(name)
        self.units = int(units)
        self.activation = activations.get(activation)
        self.use_bias = use_bias
        self.kernel_initializer = kernel_initializer
        self.dtype = dtype
        if shard not in (None, "col", "row"):
            raise ValueError(f"shard must be None/'col'/'row', got {shard!r}")
        self.shard = shard

    def sharding_hints(self):
        # Megatron-style TP: 'col' splits the output features over the model
        # axis (bias splits with them); 'row' splits the input features (the
        # partial products are summed by an XLA-inserted all-reduce, so the
        # bias stays replicated).
        if self.shard is None:
            return {}
        hints = {"kernel": self.shard}
        if self.use_bias and self.shard == "col":
            hints["bias"] = "col"
        return hints

    def init(self, key, input_shape: Shape):
        din = input_shape[-1]
        kernel = initializers.get(self.kernel_initializer)(
            key, (din, self.units), jnp.float32
        )
        params = {"kernel": kernel}
        if self.use_bias:
            params["bias"] = jnp.zeros((self.units,), jnp.float32)
        return params, {}, tuple(input_shape[:-1]) + (self.units,)

    def apply(self, params, state, x, *, train=False, rng=None):
        # Weight-only int8 (quant.py): dequantize in-trace before the
        # matmul; storage stays int8 in HBM, compute dtype is unchanged.
        kernel = maybe_dequantize(params["kernel"])
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
            kernel = kernel.astype(dt)
        y = jnp.dot(x, kernel)
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return self.activation(y), {}


class SpaceToDepth(Layer):
    """Rearrange (B, H, W, C) -> (B, H/b, W/b, C*b*b) spatial blocks.

    The TPU stem trick: a 7x7/2 conv on 3-channel input packs only 3 of the
    MXU's 128 input lanes; space-to-depth by 2 turns the same arithmetic
    into a 4x4/1 conv on 12 channels (4x the lane packing), which XLA tiles
    far better. Pure data movement — one fused reshape/transpose pass."""

    decode_safe = False  # mixes spatial positions

    def __init__(self, block_size: int = 2, name: Optional[str] = None):
        super().__init__(name)
        self.block_size = int(block_size)

    def init(self, key, input_shape: Shape):
        h, w, c = input_shape
        b = self.block_size
        if h % b or w % b:
            raise ValueError(
                f"SpaceToDepth({b}) needs spatial dims divisible by {b}; "
                f"got {(h, w)}"
            )
        return {}, {}, (h // b, w // b, c * b * b)

    def apply(self, params, state, x, *, train=False, rng=None):
        n, h, w, c = x.shape
        b = self.block_size
        x = x.reshape(n, h // b, b, w // b, b, c)
        x = x.transpose(0, 1, 3, 2, 4, 5)
        return x.reshape(n, h // b, w // b, c * b * b), {}


class Flatten(Layer):
    decode_safe = False  # collapses all non-batch axes, including time

    def init(self, key, input_shape: Shape):
        out = 1
        for d in input_shape:
            out *= d
        return {}, {}, (out,)

    def apply(self, params, state, x, *, train=False, rng=None):
        return x.reshape((x.shape[0], -1)), {}


class Activation(Layer):
    def __init__(self, activation, name=None):
        super().__init__(name)
        self.fn = activations.get(activation)

    def init(self, key, input_shape):
        return {}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        return self.fn(x), {}


class _Pool2D(Layer):
    decode_safe = False  # pooling windows span positions

    def __init__(self, pool_size: IntOr2 = 2, strides: Optional[IntOr2] = None, padding="valid", name=None):
        super().__init__(name)
        self.pool_size = _pair(pool_size)
        self.strides = _pair(strides) if strides is not None else self.pool_size
        self.padding = padding.upper()

    def init(self, key, input_shape: Shape):
        h, w, c = input_shape
        out = (
            _conv_out(h, self.pool_size[0], self.strides[0], self.padding),
            _conv_out(w, self.pool_size[1], self.strides[1], self.padding),
            c,
        )
        return {}, {}, out

    def _reduce(self, x):
        raise NotImplementedError

    def apply(self, params, state, x, *, train=False, rng=None):
        return self._reduce(x), {}


class MaxPool2D(_Pool2D):
    def _reduce(self, x):
        return lax.reduce_window(
            x,
            -jnp.inf,
            lax.max,
            window_dimensions=(1,) + self.pool_size + (1,),
            window_strides=(1,) + self.strides + (1,),
            padding=self.padding,
        )


class AvgPool2D(_Pool2D):
    def _reduce(self, x):
        ones = lax.reduce_window(
            jnp.ones_like(x),
            0.0,
            lax.add,
            window_dimensions=(1,) + self.pool_size + (1,),
            window_strides=(1,) + self.strides + (1,),
            padding=self.padding,
        )
        summed = lax.reduce_window(
            x,
            0.0,
            lax.add,
            window_dimensions=(1,) + self.pool_size + (1,),
            window_strides=(1,) + self.strides + (1,),
            padding=self.padding,
        )
        return summed / ones


class GlobalAvgPool2D(Layer):
    decode_safe = False  # reduces over spatial/temporal axes

    def init(self, key, input_shape: Shape):
        return {}, {}, (input_shape[-1],)

    def apply(self, params, state, x, *, train=False, rng=None):
        return jnp.mean(x, axis=(1, 2)), {}


class Dropout(Layer):
    needs_rng = True

    def __init__(self, rate: float, name=None):
        super().__init__(name)
        self.rate = float(rate)

    def init(self, key, input_shape):
        return {}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        if not train or self.rate == 0.0:
            return x, {}
        if rng is None:
            raise ValueError("Dropout needs an rng when train=True")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype), {}


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _bn_norm(x, mean, var, scale, bias, epsilon):
    """Normalize with given batch stats; fused-BN custom VJP.

    The custom backward (the standard fused-BN formula: dx = inv * (dy -
    mean(dy) - xhat * mean(dy*xhat))) folds the stats' gradient
    contributions into dx and returns ZERO cotangents for mean/var, so the
    stats computation upstream keeps no autodiff residuals — in particular
    no float32 copy of a bf16 activation is ever saved; backward
    recomputes xhat from the (storage-dtype) input."""
    inv = lax.rsqrt(var + epsilon) * scale
    return (x - mean.astype(x.dtype)) * inv.astype(x.dtype) + bias.astype(
        x.dtype
    )


def _bn_norm_fwd(x, mean, var, scale, bias, epsilon):
    return _bn_norm(x, mean, var, scale, bias, epsilon), (x, mean, var, scale)


def _bn_norm_bwd(epsilon, res, dy):
    x, mean, var, scale = res
    reduce_axes = tuple(range(x.ndim - 1))
    n = 1
    for a in reduce_axes:
        n *= x.shape[a]
    inv0 = lax.rsqrt(var + epsilon)  # f32 (C,)
    xhat = (x.astype(jnp.float32) - mean) * inv0
    dyf = dy.astype(jnp.float32)
    dbias = jnp.sum(dyf, axis=reduce_axes)
    dscale = jnp.sum(dyf * xhat, axis=reduce_axes)
    dx = (scale * inv0) * (dyf - dbias / n - xhat * (dscale / n))
    return (
        dx.astype(x.dtype),
        jnp.zeros_like(mean),
        jnp.zeros_like(var),
        dscale,
        dbias,
    )


_bn_norm.defvjp(_bn_norm_fwd, _bn_norm_bwd)


class BatchNorm(Layer):
    """Batch normalization over all but the channel (last) axis.

    Under data parallelism the batch axis is sharded across the mesh; because
    the stats are plain ``jnp.mean`` reductions inside the jitted step, XLA
    lowers them to cross-replica collectives automatically — i.e. this is
    sync-BN by construction, no separate "SyncBatchNorm" needed.
    """

    # Class-level default for the batch-stats reduction strategy:
    # "reduce" (jnp.mean) or "dot" (matmul against ones — see apply()).
    stats_impl = "reduce"
    # Where the conditioning shift for the single-pass moments comes from:
    # "data" (per-channel mean of the first batch element — valid on any
    # input but SERIALIZES conv -> slice-reduce -> stats, so XLA cannot fuse
    # the stat reductions into the producing conv's epilogue) or "running"
    # (the running mean from state — a constant w.r.t. this batch, so the
    # stats become epilogue siblings of the producer and the activation is
    # never re-read from HBM for statistics; measured ~26% off a
    # conv+BN site's device time, examples/profile_resnet_xplane.py).
    stats_shift = "data"

    def __init__(self, momentum: float = 0.9, epsilon: float = 1e-5,
                 stats_impl: Optional[str] = None,
                 stats_shift: Optional[str] = None, name=None):
        super().__init__(name)
        self.momentum = float(momentum)
        self.epsilon = float(epsilon)
        if stats_impl is not None:
            if stats_impl not in ("reduce", "dot"):
                raise ValueError(
                    f"stats_impl must be 'reduce' or 'dot', got {stats_impl!r}"
                )
            self.stats_impl = stats_impl
        if stats_shift is not None:
            if stats_shift not in ("data", "running"):
                raise ValueError(
                    f"stats_shift must be 'data' or 'running', got "
                    f"{stats_shift!r}"
                )
            self.stats_shift = stats_shift

    def init(self, key, input_shape: Shape):
        c = input_shape[-1]
        params = {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}
        state = {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}
        return params, state, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        reduce_axes = tuple(range(x.ndim - 1))
        if train:
            # Single-pass shifted-moment statistics: reduce (x - shift) and
            # (x - shift)^2 in one fused read of the full activation, where
            # shift is a per-channel estimate of the batch mean taken from
            # the FIRST batch element only (~H*W samples per channel — mean
            # error O(std/sqrt(HW)), a cheap serialized pre-reduce over 1/B
            # of the data). Both full reductions are then siblings over the
            # same fusion producer, so XLA emits ONE pass over HBM (the
            # naive two-pass form serializes mean -> var and reads the
            # activation twice; measured ~13ms/step extra on ResNet-50 @
            # 256). Shifting keeps E[xc^2] - E[xc]^2 well-conditioned (xc
            # is near zero-mean even when |mean| >> std, where the raw
            # E[x^2] - mu^2 form cancels catastrophically — and unlike a
            # running-mean shift, a data-derived shift is valid on the very
            # first step, when the running mean is still 0).
            # _bn_norm's custom VJP returns zero cotangents for the stats,
            # so autodiff keeps no residual of these reductions.
            # stats_shift="running" uses the running mean instead of a
            # data-derived shift: exact-arithmetic-identical (mean =
            # shift + mean(x - shift) for ANY shift), and because it is
            # constant w.r.t. the batch the reductions fuse into the
            # producing conv's epilogue instead of re-reading x. The
            # conditioning guarantee is weaker only while the running mean
            # is far from the batch mean (i.e. the first few steps, where
            # activations are near zero-mean anyway).
            if self.stats_shift == "running":
                shift = lax.stop_gradient(state["mean"])
            else:
                shift = lax.stop_gradient(
                    jnp.mean(x[:1].astype(jnp.float32), axis=reduce_axes)
                )
            if self.stats_impl == "dot":
                # Reduce via a dot against ones: XLA's reduce of a large
                # NHWC activation runs well below HBM bandwidth on some
                # TPU runtimes, while a (1, N) x (N, C) matmul streams the
                # operand at full speed through the MXU.
                n = x.size // x.shape[-1]
                x2 = x.reshape(n, x.shape[-1])
                ones = jnp.ones((1, n), jnp.float32)
                xc = x2.astype(jnp.float32) - shift
                m1 = lax.stop_gradient(jnp.dot(ones, xc)[0] / n)
                m2 = lax.stop_gradient(
                    jnp.dot(ones, jnp.square(xc))[0] / n
                )
            else:
                xc = x.astype(jnp.float32) - shift
                m1 = lax.stop_gradient(jnp.mean(xc, axis=reduce_axes))
                m2 = lax.stop_gradient(
                    jnp.mean(jnp.square(xc), axis=reduce_axes)
                )
            mean = shift + m1
            var = jnp.maximum(m2 - jnp.square(m1), 0.0)
            m = self.momentum
            new_state = {
                "mean": m * state["mean"] + (1 - m) * mean,
                "var": m * state["var"] + (1 - m) * var,
            }
            y = _bn_norm(x, mean, var, params["scale"], params["bias"],
                         self.epsilon)
            return y, new_state
        mean, var = state["mean"], state["var"]
        inv = lax.rsqrt(var + self.epsilon) * params["scale"]
        y = (x - mean.astype(x.dtype)) * inv.astype(x.dtype) + params["bias"].astype(x.dtype)
        return y, {}


class LayerNorm(Layer):
    def __init__(self, epsilon: float = 1e-6, name=None):
        super().__init__(name)
        self.epsilon = float(epsilon)

    def init(self, key, input_shape: Shape):
        d = input_shape[-1]
        params = {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
        return params, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + self.epsilon)
        y = y * params["scale"] + params["bias"]
        return y.astype(x.dtype), {}


class RMSNorm(Layer):
    """``x / sqrt(mean(x^2) + epsilon) * scale`` over the last axis, in
    float32, returned in ``x``'s dtype. No mean subtraction and no bias
    (Zhang & Sennrich 2019; the norm of the DeepSeek-V3 block)."""

    def __init__(self, epsilon: float = 1e-6, name=None):
        super().__init__(name)
        self.epsilon = float(epsilon)

    def default_name(self) -> str:
        return "rms_norm"  # the camel-case splitter would produce "rmsnorm"

    def init(self, key, input_shape: Shape):
        d = input_shape[-1]
        return {"scale": jnp.ones((d,), jnp.float32)}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + self.epsilon) * params["scale"]
        return y.astype(x.dtype), {}


class GatedMLP(Layer):
    """Bias-free gated MLP, ``down(act(gate(x)) * up(x))``, on the trailing
    axis (SwiGLU with ``activation="silu"``). Its three kernels sit under
    the keys ``dense`` (gate), ``dense_1`` (up) and ``dense_2`` (down), so
    the parameter path, which is the device scope, reads ``dense*`` as the
    plain MLP's does; gate and up are column-sharded, down row-sharded."""

    def __init__(self, hidden_dim: int, activation="silu", dtype=None,
                 kernel_initializer="glorot_uniform", name=None):
        super().__init__(name)
        self.hidden_dim = int(hidden_dim)
        self.activation = activations.get(activation)
        self.kernel_initializer = kernel_initializer
        self.dtype = dtype

    def init(self, key, input_shape: Shape):
        d, h = input_shape[-1], self.hidden_dim
        init = initializers.get(self.kernel_initializer)
        keys = jax.random.split(key, 3)
        params = {
            name: {"kernel": init(k, shape, jnp.float32)}
            for name, k, shape in zip(
                ("dense", "dense_1", "dense_2"), keys,
                ((d, h), (d, h), (h, d)))
        }
        return params, {}, tuple(input_shape)

    def sharding_hints(self):
        return {"dense": {"kernel": "col"}, "dense_1": {"kernel": "col"},
                "dense_2": {"kernel": "row"}}

    def apply(self, params, state, x, *, train=False, rng=None):
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)

        def linear(name, h):
            kernel = maybe_dequantize(params[name]["kernel"])
            with child_scope(name):
                return jnp.dot(h, kernel.astype(h.dtype))

        hidden = self.activation(linear("dense", x)) * linear("dense_1", x)
        return linear("dense_2", hidden), {}


def _shifted(a, back: int):
    """``a`` (..., T, D) moved ``back`` positions along T, zeros moved in:
    ``out[t] = a[t - back]``."""
    if back == 0:
        return a
    pad = [(0, 0)] * (a.ndim - 2) + [(back, 0), (0, 0)]
    return lax.slice_in_dim(jnp.pad(a, pad), 0, a.shape[-2], axis=-2)


@jax.checkpoint
def gated_taps(bcz, taps):
    """The gated short convolution between its two products: with (b, c, z)
    = split3(``bcz``) (..., T, 3D) and ``taps`` (K, D) float32,

        g = b * z;  h[t] = sum_j taps[j] * g[t - (K - 1) + j];  out = c * h

    written as the equation reads (K shifted copies, K multiply-adds), the
    products and sums in float32, returned in ``bcz``'s dtype. A
    ``jax.checkpoint``: the backward pass keeps ``bcz`` alone, as it arrived,
    and computes g and h again. Without it autodiff keeps the float32 copies
    of what it multiplied by, up to 20 bytes a channel and token where
    ``bcz`` in bfloat16 is 6 (v5e compile, PR 34: the in-projection's output
    was held as float32, 201 MB a layer at T = 8192, and the step's
    temporaries read 7.50 GB where they now read 6.53)."""
    b, c, z = jnp.split(bcz, 3, axis=-1)
    k = taps.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    # g's shifted copies as products of b's and z's shifted copies, each
    # widened after its shift: every term then reads the layer's input at an
    # offset and XLA makes one pass of it in the input's dtype (a g computed
    # once and read at K offsets, or b and z widened first, it writes to
    # memory as float32 before the taps: v5e compile, PR 34).
    h = sum(taps[j] * f32(_shifted(b, k - 1 - j)) * f32(_shifted(z, k - 1 - j))
            for j in range(k))
    return (f32(c) * h).astype(bcz.dtype)


class ShortConv(Layer):
    """LFM2's gated short convolution over (B, T, D) inputs (Liquid AI's
    ``Lfm2ShortConv``; ``model_type: lfm2`` / ``lfm2_moe``), a token mixer
    that looks ``kernel_size - 1`` positions back and no further:

        (b, c, z) = split3(x W_in)                   W_in (D, 3D), this order
        g = b * z
        h[t] = sum_j taps[j] * g[t - (K - 1) + j]    taps (K, D), g[< 0] = 0
        out = (c * h) W_out                          W_out (D, D)

    a depthwise causal convolution (one tap a channel and offset) between
    two elementwise gates and two matrix products; no bias anywhere. The
    part between the products is ``gated_taps``: memory-bound elementwise
    work that XLA fuses into a few passes over ``x W_in`` (1.6 ms a layer,
    both passes, of the 7.9 the layer takes at T = 8192, D = 2048 on the
    v5e: root PERF.md, PR 34). Its kernels
    ``w_in``, ``taps`` and ``w_out`` sit under the layer's own key,
    ``short_conv``, which is its device scope; inside it ``mix`` holds the
    gates and the taps without the two products. The taps start uniform in
    +-1/sqrt(K), PyTorch's default for the ``Conv1d`` they are published as.

    For training and full forward passes: no cached decode (a rolling state
    of K - 1 rows a layer does not exist in ``serving/`` yet, ROADMAP.md)."""

    decode_safe = False  # reads the K - 1 positions before each one

    def __init__(self, kernel_size: int = 3, dtype=None,
                 name: Optional[str] = None):
        super().__init__(name)
        self.kernel_size = int(kernel_size)
        if self.kernel_size < 1:
            raise ValueError(f"kernel_size must be >= 1, got {kernel_size}")
        self.dtype = dtype

    def init(self, key, input_shape: Shape):
        d, k = input_shape[-1], self.kernel_size
        k_in, k_taps, k_out = jax.random.split(key, 3)
        glorot = initializers.get("glorot_uniform")
        bound = k ** -0.5
        params = {
            "w_in": glorot(k_in, (d, 3 * d), jnp.float32),
            "taps": jax.random.uniform(k_taps, (k, d), jnp.float32,
                                       -bound, bound),
            "w_out": glorot(k_out, (d, d), jnp.float32),
        }
        return params, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
        bcz = jnp.dot(x, maybe_dequantize(params["w_in"]).astype(x.dtype))
        with child_scope("mix"):
            y = gated_taps(bcz, params["taps"].astype(jnp.float32))
        return jnp.dot(y, maybe_dequantize(params["w_out"]).astype(x.dtype)
                       ), {}


class Embedding(Layer):
    """A table of ``vocab_size`` rows of ``dim``, drawn normal(``stddev``)."""

    def __init__(self, vocab_size: int, dim: int, dtype=None,
                 stddev: float = 0.02, name=None):
        super().__init__(name)
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)
        self.dtype = dtype
        self.stddev = float(stddev)

    def init(self, key, input_shape: Shape):
        table = initializers.normal(self.stddev)(
            key, (self.vocab_size, self.dim), jnp.float32)
        return {"table": table}, {}, tuple(input_shape) + (self.dim,)

    def apply(self, params, state, x, *, train=False, rng=None):
        table = params["table"]
        dt = resolve_dtype(self.dtype)
        if is_quantized_leaf(table):
            # Gather int8 rows FIRST, dequantize only the gathered rows
            # (per-channel scales broadcast over the trailing dim) — the
            # full f32 table never materializes on the decode path.
            rows = jnp.take(table["q"], x, axis=0).astype(jnp.float32)
            rows = rows * table["scale"]
            return rows if dt is None else rows.astype(dt), {}
        if dt is not None:
            table = table.astype(dt)
        return jnp.take(table, x, axis=0), {}


class TiedHead(Layer):
    """Bias-free logits ``x E^T`` over the ``units`` rows of an embedding's
    table ``E`` (units, D) that is another layer's leaf: this layer owns
    none, and ``nn.TiedSequential`` hands it the embedding's parameters. Its
    key, and so its device scope, is ``dense``: where an untied head's
    product sits (``benchmarks/scopes.py`` files a top-level ``dense*``
    under the head)."""

    def __init__(self, units: int, dtype=None, name: Optional[str] = None):
        super().__init__(name)
        self.units = int(units)
        self.dtype = dtype

    def default_name(self) -> str:
        return "dense"

    def init(self, key, input_shape: Shape):
        return {}, {}, tuple(input_shape[:-1]) + (self.units,)

    def apply(self, params, state, x, *, train=False, rng=None):
        if "table" not in params:
            raise ValueError(
                f"{self.name!r} owns no table: it is applied by "
                "nn.TiedSequential, which hands it the embedding's (the "
                "decode paths and head_chunks apply it on its own subtree)")
        table = maybe_dequantize(params["table"])
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
        return lax.dot_general(
            x, table.astype(x.dtype),
            (((x.ndim - 1,), (1,)), ((), ()))), {}
