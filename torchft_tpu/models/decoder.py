"""What every decoder here shares, written once: rotary, the norm, the output
head with its fused loss, the ``dots`` remat rule and the layer stack (of one
kind of block, or of a period of kinds; run once, or by :func:`looped_stack`
several times on ONE set of parameters). A model file (models/llama.py,
models/keye.py, models/smallthinker.py, models/granite.py, models/ouro.py) is
a config, a block and a top-level module of embedding, :func:`layer_stack`,
final norm and head; its attention asks ops/ for a kernel (ops/attention.py,
ops/sparse_attention.py), and a routed expert layer is models/experts.py's.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchft_tpu.ops.cross_entropy import chunked_cross_entropy, chunked_cross_entropy_by_token

__all__ = [
    "apply_rope", "RMSNorm", "LMHead", "tied_head", "into_residual", "remat_policy",
    "layer_stack", "looped_stack", "smallest_period", "sown_by_layer",
]


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (batch, seq, heads, head_dim); positions: (batch, seq)."""
    head_dim = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    """float32 accumulation whatever ``dtype`` is; the scale is STORED in
    ``param_dtype`` (``KeyeConfig.norm_dtype`` says why that is an option)."""

    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (normed * scale.astype(jnp.float32)).astype(self.dtype)


class LMHead(nn.Module):
    """The output projection, param-compatible with ``nn.Dense`` (same
    ``lm_head/kernel`` path, lecun-normal init, dtype promotion): owning
    the kernel directly lets the fused loss path hand it to
    :func:`~torchft_tpu.ops.cross_entropy.chunked_cross_entropy` without
    ever forming the logits. With ``targets`` the mean token cross-entropy,
    in vocabulary slabs of ``loss_vocab_chunk`` (None = dense), or with
    ``by_token`` every token's own (a model with several exits calls the one
    head once an exit and weighs the tokens itself)."""

    dim: int
    vocab_size: int
    dtype: Any = jnp.bfloat16
    loss_vocab_chunk: Optional[int] = None

    @nn.compact
    def __call__(
        self, x: jnp.ndarray, targets: Optional[jnp.ndarray] = None, by_token: bool = False
    ) -> jnp.ndarray:
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (self.dim, self.vocab_size), self.dtype
        )
        if targets is None:
            return jnp.dot(x, kernel.astype(self.dtype))
        loss = chunked_cross_entropy_by_token if by_token else chunked_cross_entropy
        return loss(x, kernel, targets, self.loss_vocab_chunk)


def tied_head(
    embed: nn.Embed, x: jnp.ndarray, targets: Optional[jnp.ndarray] = None,
    loss_vocab_chunk: Optional[int] = None,
) -> jnp.ndarray:
    """The output head of a model whose head IS its embedding (``embed``, the
    bound module, so its one ``(vocab, dim)`` matrix is the only leaf): with
    ``targets`` the mean token cross-entropy of ``softmax(x @ embedding.T)``
    through :func:`~torchft_tpu.ops.cross_entropy.chunked_cross_entropy`,
    which never forms the logits; without, the logits. The matrix then has
    two uses in one program, the gather and this product, and autodiff gives
    it the sum of both gradients."""
    if targets is None:
        return embed.attend(x)
    return chunked_cross_entropy(x, embed.embedding.T, targets, loss_vocab_chunk)


def into_residual(depth: int, **axes):
    """The initialiser of a projection INTO the residual stream (an attention's
    output projection, a gated unit's way back) of a pre-norm stack ``depth``
    layers deep: lecun-normal over sqrt(2 x depth), the scaled initialisation
    of deep pre-norm stacks (``KeyeConfig.init_depth`` says what it is for).
    ``axes``: ``variance_scaling``'s, for a kernel with more than two."""
    return nn.initializers.variance_scaling(1.0 / (2 * depth), "fan_in", "truncated_normal", **axes)


def remat_policy(remat: str, dots: Any, *names: str):
    """The policy of ``remat`` for :func:`layer_stack`. ``dots`` keeps what
    the model's ``dots`` policy picks by primitive (the ``dot_general`` results
    of ``jax.checkpoint_policies.checkpoint_dots`` or a narrower one; the
    router's decisions of models/experts.py ``routing_saveable``; nothing),
    and the arrays tagged ``names``: a Pallas call is no ``dot_general``, so
    without the flash forward kernel's (out, logsumexp) by name the backward
    would run that whole kernel a second time. ``full`` (None) recomputes
    everything, that kernel included."""
    if remat != "dots":
        return None
    policies = jax.checkpoint_policies
    return policies.save_from_both_policies(dots, policies.save_only_these_names(*names))


class _ScanCell(nn.Module):
    """One block in ``(carry, broadcast) -> (carry, out)`` shape for
    ``nn.scan``; params live under ``<stack>/block`` with a leading layer
    axis added by the scan's ``variable_axes``."""

    block: Any  # the block's class
    config: Any

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray):
        return self.block(self.config, name="block")(x, positions), None


class _PeriodCell(nn.Module):
    """One period of blocks, kinds 0 .. period - 1 one after the other, in
    the shape ``nn.scan`` wants; params live under ``<stack>/block_<kind>``
    with a leading axis of periods."""

    block: Any  # the block's class (rematerialised already, where asked)
    config: Any
    period: int

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray):
        for kind in range(self.period):
            x = self.block(self.config, kind, name=f"block_{kind}")(x, positions)
        return x, None


def _scanned(cell: Any, length: int):
    """``cell`` scanned ``length`` times: params and intermediates gain a
    leading axis, positions are broadcast."""
    return nn.scan(
        cell,
        variable_axes={"params": 0, "intermediates": 0},
        split_rngs={"params": True},
        length=length,
        in_axes=nn.broadcast,
    )


def smallest_period(kinds) -> int:
    """The smallest number of layers a sequence of layer kinds repeats with:
    what a model whose config lists a kind for every layer hands
    :func:`layer_stack` as ``period``."""
    n = len(kinds)
    return next(
        p for p in range(1, n + 1)
        if n % p == 0 and all(kinds[i] == kinds[i % p] for i in range(n))
    )


def layer_stack(
    block: Any, cfg: Any, policy: Any, x: jnp.ndarray, positions: jnp.ndarray, period: int = 1
):
    """``cfg.n_layers`` of ``block(cfg)(x, positions) -> x``, called inside the
    model's ``__call__``. ``cfg.scan_layers``: one ``lax.scan`` over the stack,
    one traced and compiled block for the whole depth (O(1) HLO size and
    compile time in depth), leaves under ``layers/block/...`` with a leading
    layer axis (as is what a block sows into ``intermediates``); otherwise
    inlined copies under ``layer_<i>/...``. ``cfg.remat`` other than ``none``
    rematerialises each block under ``policy`` (:func:`remat_policy`).

    ``period`` > 1: the depth repeats a period of that many KINDS of block,
    layer i of kind ``i % period``, built ``block(cfg, kind)``. Scanned, the
    scan is over the periods: one traced period for the whole depth, leaves
    under ``layers/block_<kind>/...`` with a leading axis of ``n_layers /
    period``, each block of the period rematerialised on its own. Inlined,
    ``layer_<i>`` as before. A stack of one kind is the tree it always was:
    the two scanned paths below (the whole cell rematerialised for one kind,
    each block of the period for several) are kept apart for that alone, so
    that a one-kind stack keeps its parameter names and its compiled program
    (heals, checkpoints and the benchmark's cells read both)."""
    if period > 1:
        if cfg.n_layers % period:
            raise ValueError(f"{cfg.n_layers} layers are not whole periods of {period}")
        if cfg.remat != "none":
            # A scan of ONE period is no loop to XLA: it inlines the body, the
            # forward and its recomputation then meet in one computation, and
            # without the barrier CSE merges them and keeps every activation.
            inlined = not cfg.scan_layers or cfg.n_layers == period
            block = nn.remat(block, policy=policy, prevent_cse=inlined)
        if cfg.scan_layers:
            stack = _scanned(_PeriodCell, cfg.n_layers // period)
            return stack(block, cfg, period, name="layers")(x, positions)[0]
        for layer in range(cfg.n_layers):
            x = block(cfg, layer % period, name=f"layer_{layer}")(x, positions)
        return x
    if cfg.scan_layers:
        cell = _ScanCell
        if cfg.remat != "none":
            # prevent_cse is safe (and standard) under scan: the loop
            # boundary already blocks the CSE remat would otherwise fight.
            cell = nn.remat(cell, policy=policy, prevent_cse=False)
        return _scanned(cell, cfg.n_layers)(block, cfg, name="layers")(x, positions)[0]
    for layer in _inlined_layers(block, cfg, policy):
        x = layer(x, positions)
    return x


def _inlined_layers(block: Any, cfg: Any, policy: Any) -> list:
    """The blocks of a one-kind stack as modules ``layer_<i>`` of the calling
    model, each rematerialised on its own where ``cfg.remat`` asks."""
    if cfg.remat != "none":
        block = nn.remat(block, policy=policy)
    return [block(cfg, name=f"layer_{layer}") for layer in range(cfg.n_layers)]


def looped_stack(
    model: nn.Module, block: Any, cfg: Any, policy: Any, x: jnp.ndarray, positions: jnp.ndarray,
    loops: int, after_pass: Any,
) -> jnp.ndarray:
    """:func:`layer_stack` run ``loops`` times on ONE set of parameters, the
    state carried from pass to pass: ``x <- after_pass()(layer_stack(x))``,
    ``after_pass()`` the module every pass ends in (the final norm, shared by
    the passes like the layers). Returns every pass's state, ``(loops, *x.shape)``.
    Called inside ``model``'s ``__call__`` with the model itself.

    The tree is :func:`layer_stack`'s (``layers/block/...`` with a leading axis
    of layers, or ``layer_<i>/...``), ONE copy whatever ``loops`` is: under
    ``cfg.scan_layers`` the passes are a scan too whose parameters are
    BROADCAST to every pass (``variable_broadcast``: closed over, not stacked
    along the loop's axis), one traced pass for all of them; otherwise the
    same modules are called again pass after pass. Either way a weight's
    gradient is the sum of its ``loops`` uses, which autodiff carries in the
    weight's own dtype. A block may not sow inside the passes (nothing of a
    scan over broadcast parameters is stacked by pass)."""
    if cfg.scan_layers:
        def one_pass(_model, carry, _):
            with jax.named_scope("tpuft::loop_pass"):
                carry = layer_stack(block, cfg, policy, carry, positions)
            carry = after_pass()(carry)
            return carry, carry

        passes = nn.scan(
            one_pass, variable_broadcast="params", split_rngs={"params": False}, length=loops
        )
        return passes(model, x, None)[1]
    layers, ends_in, states = _inlined_layers(block, cfg, policy), after_pass(), []
    for _ in range(loops):
        with jax.named_scope("tpuft::loop_pass"):
            for layer in layers:
                x = layer(x, positions)
        x = ends_in(x)
        states.append(x)
    return jnp.stack(states)


def sown_by_layer(
    model: nn.Module, params: Any, tokens: jnp.ndarray, module: str, name: str
) -> jnp.ndarray:
    """What the blocks' submodule ``module`` sowed into ``intermediates`` under
    ``name`` for ``tokens`` (b, s), by layer: (n_layers, ...), from any layout
    of :func:`layer_stack`'s tree. Where only some kinds of a period have the
    submodule, the layers that have it, in their order."""
    _, seen = model.apply(params, tokens, mutable=["intermediates"])
    seen = seen["intermediates"]
    if "layers" not in seen:
        layers = sorted((k for k in seen if k.startswith("layer_")), key=lambda k: int(k[6:]))
        return jnp.stack([seen[k][module][name][0] for k in layers if module in seen[k]])
    stack = seen["layers"]
    if "block" in stack:
        return stack["block"][module][name][0]
    kinds = sorted((k for k in stack if module in stack[k]), key=lambda k: int(k[6:]))
    by_period = jnp.stack([stack[k][module][name][0] for k in kinds], axis=1)
    return by_period.reshape(-1, *by_period.shape[2:])  # (periods x kinds, ...)
