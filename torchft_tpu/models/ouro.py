"""Looped decoder (the ``Ouro`` family, "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): ONE stack of layers run ``loops`` times
on one set of weights, an exit after every pass, the exits weighed token by
token by a learned gate.

Block ``i``, four RMSNorms in a sandwich (a norm before AND after each branch):

    a = x + N2_i(Attn_i(N1_i(x)))
    y = a + N4_i(MLP_i(N3_i(a)))

``Attn``: q, k, v, o without bias, ``n_heads`` query and ``n_kv_heads`` key-value
heads of ``dim / n_heads``, rotary (rotate-half) over the whole head on q and k,
causal softmax of ``q k^T / sqrt(head_dim)``; one call into ops/attention.py
``attend``. ``MLP``: ``W_down(silu(h W_gate) * (h W_up))``.

The loop: ``h_0 = E[tokens]``; for ``t = 1 .. loops``: ``h_t = N_f(M(h_{t-1}))``
with ``M`` the whole stack, the SAME weights and the same final norm ``N_f`` in
every pass (models/decoder.py ``looped_stack``: the parameter tree is the
one-kind tree of every decoder here, ``layers/block/...``, ONE copy). The
normed state is both the exit's input and the next pass's.

Exit ``t``: logits ``h_t W_head``; gate ``lambda_t = sigmoid(h_t . w_g + b_g)``,
one ``Linear(dim, 1)`` shared by the passes. By token, the probability of
leaving at exit t (:func:`exit_log_probs`):

    p_1 = lambda_1,   p_t = lambda_t prod_{j<t} (1 - lambda_j),
    p_T = prod_{j<T} (1 - lambda_j)      (the last pass takes what is left)

and the training loss (the paper's stage I) is the mean over tokens of

    sum_t p_t CE_t  -  beta H(p),     H(p) = -sum_t p_t log p_t,

``CE_t`` the next-token cross-entropy of exit t (ops/cross_entropy.py's loss BY
TOKEN through the one head, ``loops`` times a step, no logits formed), the
gate's arithmetic in float32 from ``log_sigmoid`` (never ``log(1 - lambda)``).
Training always runs every pass; without ``targets`` the model gives the last
exit's logits (inference that never leaves early). It sows ``exit_probs`` and
``exit_ce``, the mean ``p_t`` and ``CE_t`` by pass (:func:`exit_stats`).

Each of the ``n_layers x loops`` layer passes keeps for its backward what
``remat`` says; ``dots`` in THIS model is by name (``Ouro.__call__``).
No sharding plan yet: the model runs on one device or replicated.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from torchft_tpu.models.decoder import LMHead, RMSNorm, apply_rope, looped_stack, remat_policy
from torchft_tpu.ops.attention import attend
from torchft_tpu.ops.flash_attention import FLASH_LSE, FLASH_OUT

__all__ = ["OuroConfig", "Ouro", "exit_log_probs", "exit_stats"]

# checkpoint_name tag of the unit's output (w_down's result), the one projection
# result ``dots`` keeps by name beside the flash kernels' pair.
MLP_OUT = "ouro_mlp_out"


@dataclass(frozen=True)
class OuroConfig:
    vocab_size: int = 49152
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 16  # of dim / n_heads each
    n_kv_heads: int = 16
    ffn_hidden: int = 5632
    loops: int = 4  # passes through the one stack (``total_ut_steps``)
    exit_entropy_coef: float = 0.05  # beta
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # What the norms' scales and the exit gate's two leaves are STORED in.
    norm_dtype: Any = jnp.float32
    # The path ops/attention.py ``attend`` takes, as models/llama.py.
    attention_impl: str = "auto"
    # "none" | "full" | "dots". ``dots`` is by NAME here: the flash pair and
    # w_down's result, not every dot_general as in models/llama.py.
    remat: str = "none"
    loss_vocab_chunk: Optional[int] = None
    scan_layers: bool = False

    def __post_init__(self) -> None:
        if self.attention_impl not in ("auto", "dense", "blockwise", "flash"):
            raise ValueError(f"attention_impl={self.attention_impl!r}")
        if self.remat not in ("none", "full", "dots"):
            raise ValueError(f"remat={self.remat!r} is not one of ('none', 'full', 'dots')")
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} / {self.n_kv_heads} heads over {self.dim}")
        if self.loops < 1:
            raise ValueError(f"loops={self.loops}: the stack runs once at the least")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads


def _dense(cfg: OuroConfig):
    return partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.dtype)


class Attention(nn.Module):
    config: OuroConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dense = _dense(cfg)
        q = dense(features=(cfg.n_heads, cfg.head_dim), name="wq")(x)
        k = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wk")(x)
        v = dense(features=(cfg.n_kv_heads, cfg.head_dim), name="wv")(x)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        with jax.named_scope("tpuft::ouro_attention"):
            # The flash kernels' blocks are the other models': 512 x 1024.
            out = attend(q, k, v, scale=cfg.head_dim**-0.5, impl=cfg.attention_impl, block_k=1024)
        return dense(features=cfg.dim, axis=(-2, -1), name="wo")(out)


class MLP(nn.Module):
    config: OuroConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        dense = _dense(cfg)
        gate = dense(features=cfg.ffn_hidden, name="w_gate")(x)
        up = dense(features=cfg.ffn_hidden, name="w_up")(x)
        return checkpoint_name(dense(features=cfg.dim, name="w_down")(nn.silu(gate) * up), MLP_OUT)


class Block(nn.Module):
    """A norm before each branch and one after it, ahead of the residual sum."""

    config: OuroConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        norm = partial(RMSNorm, cfg.norm_eps, cfg.dtype, cfg.norm_dtype)
        branch = Attention(cfg, name="attn")(norm(name="attn_norm")(x), positions)
        x = x + norm(name="attn_post_norm")(branch)
        branch = MLP(cfg, name="mlp")(norm(name="mlp_norm")(x))
        return x + norm(name="mlp_post_norm")(branch)


def exit_log_probs(gate_logits: jnp.ndarray) -> jnp.ndarray:
    """``log p_t`` by token from the gate's logits ``(loops, ...)``, float32:
    ``log p_t = sum_{j<t} log(1 - lambda_j) + log lambda_t`` and, for the last
    exit, the sum alone: it takes what is left, so ``exp`` of the result sums
    to one over the exits and the last pass's logit takes no part."""
    z = gate_logits.astype(jnp.float32)[:-1]
    stays = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)  # sum_{j<=t} log(1 - lambda_j)
    none = jnp.zeros_like(gate_logits[:1], jnp.float32)
    before = jnp.concatenate([none, stays])  # sum_{j<t}, t = 1 .. loops
    leaves = jnp.concatenate([jax.nn.log_sigmoid(z), none])  # log lambda_t; 0 for the last
    return before + leaves


class Ouro(nn.Module):
    """``apply(params, tokens)`` returns the last exit's logits;
    ``apply(params, tokens, targets=targets)`` the training loss above, the
    head's products through the fused loss where ``loss_vocab_chunk`` is set."""

    config: OuroConfig

    @nn.compact
    def __call__(
        self, tokens: jnp.ndarray, positions: Optional[jnp.ndarray] = None,
        targets: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        cfg = self.config
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        x = nn.Embed(
            cfg.vocab_size, cfg.dim, dtype=cfg.dtype, param_dtype=cfg.dtype, name="tok_embed"
        )(tokens)
        # ``dots`` keeps by name, of each of the n_layers x loops layer passes:
        # the flash kernels' output and logsumexp (a Pallas call the backward
        # would otherwise run again) and w_down's result, which the norm after
        # the branch reads in its backward; NO other ``dot_general`` result
        # (far less than models/llama.py's ``dots``, whose every projection
        # does not fit here): q, k, v, wo's result, gate and up come again in
        # the layer's backward. Measured on the chip at 8 layers
        # x 4 passes x 8192 tokens (PERF.md section 6, PR 62): 1,017.7 ms a
        # step and 13.49 of 15.75 GiB; gate and up besides do not fit, wo's
        # result besides is slower, nothing besides the flash pair 2.5% slower.
        policy = remat_policy(
            cfg.remat, jax.checkpoint_policies.nothing_saveable, FLASH_OUT, FLASH_LSE, MLP_OUT
        )

        def final_norm():
            norm = RMSNorm(cfg.norm_eps, cfg.dtype, cfg.norm_dtype, name="final_norm")

            def ends_in(h):
                with jax.named_scope("tpuft::exit"):
                    return norm(h)

            return ends_in

        states = looped_stack(self, Block, cfg, policy, x, positions, cfg.loops, final_norm)
        head = LMHead(cfg.dim, cfg.vocab_size, cfg.dtype, cfg.loss_vocab_chunk, name="lm_head")
        gate = nn.Dense(
            1, dtype=jnp.float32, param_dtype=cfg.norm_dtype, name="exit_gate",
            kernel_init=nn.initializers.lecun_normal(),
        )
        if targets is None:
            # Inference that never leaves early reads no gate; the call gives the
            # gate its leaves when this path initialises, and is dead code under jit.
            gate(states[-1])
            return head(states[-1]).astype(jnp.float32)
        gate_logits, losses = [], []
        for state in states:
            with jax.named_scope("tpuft::exit"):
                gate_logits.append(gate(state)[..., 0])
                losses.append(head(state, targets, by_token=True))
        log_p = exit_log_probs(jnp.stack(gate_logits))  # (loops, b, s)
        p, losses = jnp.exp(log_p), jnp.stack(losses)
        self.sow("intermediates", "exit_probs", jnp.mean(p, axis=(1, 2)))
        self.sow("intermediates", "exit_ce", jnp.mean(losses, axis=(1, 2)))
        # sum_t p_t CE_t - beta H(p), H(p) = -sum_t p_t log p_t, by token.
        by_token = jnp.sum(p * (losses + cfg.exit_entropy_coef * log_p), axis=0)
        return jnp.mean(by_token)


def exit_stats(model: Ouro, params: Any, tokens: jnp.ndarray, targets: jnp.ndarray) -> Dict[str, Any]:
    """``{"exit_probs", "exit_ce"}``, each ``(loops,)``: the mean probability of
    leaving at each exit and each exit's mean cross-entropy, for ``tokens`` and
    ``targets`` (b, s)."""
    _, seen = model.apply(params, tokens, targets=targets, mutable=["intermediates"])
    return {name: seen["intermediates"][name][0] for name in ("exit_probs", "exit_ce")}
