"""The routed expert layer as ONE CHIP'S SHARE of an expert-parallel layer,
written once for the models that have one (models/keye.py,
models/smallthinker.py).

A router scores every token against ALL ``num_experts``; the
``experts_per_token`` largest are chosen and their softmax weights
renormalised to one (which is the softmax over the chosen logits alone). The
layer HOLDS ``num_local_experts`` of the experts, ``expert_share *
num_local_experts ..``, and computes their part of the result: the rows routed
here, sorted by expert, through one grouped product a matrix
(ops/grouped_matmul.py ``routed_experts``). Dropless and exact: the row buffer
follows the rows that arrive, up a short ladder of row counts chosen each layer
step from the router's own tally, whose last rung is the worst case, every
token's every choice. What the absent experts would add is left out and
nothing stands in for their chips: on one chip the layer runs without its
exchange. ``num_local_experts == num_experts`` is the whole layer, one path at
the worst case.

The router's logits are an argument of the layer, so a block decides what the
router reads: the layer's own normed input (``logits=None``: models/keye.py)
or the block's input ahead of attention (``RoutedExperts.logits`` called
there: models/smallthinker.py). The gated unit's activation is a field.

What ``route`` decides need not be decided again in the layer's backward:
``order``, ``gates`` and ``group_sizes`` (the grouped product's operands and so
its backward's residuals) carry the ``checkpoint_name`` tag ``ROUTING``, and
the top-k's values and indices, which ``lax.top_k``'s own differentiation rule
reads before anything could name them, are kept by primitive. Sixteen bytes a
choice and the tally, where a remat policy that keeps neither (no
``dot_general`` made them) runs the top-k, the sort and the tally a second
time. ``routing_saveable`` is the policy that keeps both; a model's ``dots``
may hold it (models/decoder.py ``remat_policy``; models/smallthinker.py's
does), and where no policy does the tag does nothing.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from torchft_tpu.models.decoder import sown_by_layer
from torchft_tpu.ops.grouped_matmul import routed_experts

__all__ = ["RoutedExperts", "route", "router_load", "dispatch_rows", "routing_saveable"]

# checkpoint_name tag of what :func:`route` hands the layer (module docstring).
ROUTING = "routing"


_tagged_routing = jax.checkpoint_policies.save_only_these_names(ROUTING)


def routing_saveable(prim: Any, *_: Any, **params: Any) -> bool:
    """The remat policy that keeps what :func:`route` decides: the arrays
    tagged ``ROUTING`` and the outputs of ``lax.top_k`` (a block has the one)."""
    return prim is jax.lax.top_k_p or _tagged_routing(prim, **params)


def route(probs: jnp.ndarray, experts_per_token: int, num_local_experts: int, expert_share: int):
    """probs (n, num_experts) -> for each of the n x experts_per_token choices,
    in the order the grouped product wants them: ``order`` (which choice sits
    in each row: choices sorted by held expert, those for experts held
    elsewhere last), ``gates`` (n, k) renormalised over the k chosen, and
    ``group_sizes`` (num_local_experts + 1,), rows by held expert and, last,
    the rows that belong elsewhere."""
    local = num_local_experts
    decided = partial(checkpoint_name, name=ROUTING)
    top, experts = jax.lax.top_k(probs, experts_per_token)
    gates = decided(top / jnp.sum(top, axis=-1, keepdims=True))
    mine = experts - expert_share * local
    group = jnp.where((mine >= 0) & (mine < local), mine, local).reshape(-1)
    order = decided(jnp.argsort(group, stable=True))
    group_sizes = decided(jnp.bincount(group, length=local + 1).astype(jnp.int32))
    return order, gates, group_sizes


class RoutedExperts(nn.Module):
    """``layer(x)`` or ``layer(x, layer.logits(other))``: x (b, s, dim) ->
    the held experts' part of the routed layer's result, (b, s, dim). Leaves:
    ``router/kernel`` (dim, num_experts), ``w_gate`` and ``w_up``
    (num_local_experts, dim, hidden), ``w_down`` (num_local_experts, hidden,
    dim), stored and multiplied in ``dtype``; the router's products come out
    in float32 unrounded. Sows ``rows_by_expert`` (rows each held expert
    receives) and ``dispatch_rows`` (the rung the dispatch ran at) into
    ``intermediates``."""

    dim: int
    hidden: int
    num_experts: int  # the router's width
    experts_per_token: int
    num_local_experts: int  # held here
    expert_share: int = 0  # which share: experts share * local .. + local - 1
    activation: Callable = nn.silu
    dtype: Any = jnp.bfloat16
    down_init: Optional[Callable] = None  # of ``w_down``; None: lecun-normal by expert

    def setup(self) -> None:
        local, d, f = self.num_local_experts, self.dim, self.hidden
        axes = dict(in_axis=-2, out_axis=-1, batch_axis=0)
        init = nn.initializers.lecun_normal(**axes)
        self.w_gate = self.param("w_gate", init, (local, d, f), self.dtype)
        self.w_up = self.param("w_up", init, (local, d, f), self.dtype)
        self.w_down = self.param("w_down", self.down_init or init, (local, f, d), self.dtype)
        self.router = nn.DenseGeneral(
            features=self.num_experts, use_bias=False, dtype=self.dtype, param_dtype=self.dtype,
            dot_general=partial(jax.lax.dot_general, preferred_element_type=jnp.float32),
        )

    def logits(self, x: jnp.ndarray) -> jnp.ndarray:
        """The router's float32 scores of ``x`` (..., dim): (..., num_experts)."""
        return self.router(x).astype(jnp.float32)

    def __call__(self, x: jnp.ndarray, logits: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        b, s, d = x.shape
        n, local = b * s, self.num_local_experts
        with jax.named_scope("tpuft::expert_layer"):
            flat = x.reshape(n, d)
            if logits is None:
                logits = self.logits(flat)
            probs = jax.nn.softmax(logits.reshape(n, self.num_experts), axis=-1)
            order, gates, group_sizes = route(
                probs, self.experts_per_token, local, self.expert_share
            )
            self.sow("intermediates", "rows_by_expert", group_sizes[:local])
            out, rows = routed_experts(
                flat, order, gates, group_sizes, self.w_gate, self.w_up, self.w_down,
                num_experts=self.num_experts, activation=self.activation,
            )
            self.sow("intermediates", "dispatch_rows", rows)
            return out.astype(self.dtype).reshape(b, s, d)


def router_load(model: nn.Module, params: Any, tokens: jnp.ndarray) -> jnp.ndarray:
    """Rows each held expert receives for ``tokens`` (b, s), by layer of a
    model whose blocks name their routed layer ``moe``: (n_layers,
    num_local_experts). Dropless, so they are all computed; their expectation
    is ``b * s * experts_per_token / num_experts`` each."""
    return sown_by_layer(model, params, tokens, "moe", "rows_by_expert")


def dispatch_rows(model: nn.Module, params: Any, tokens: jnp.ndarray) -> jnp.ndarray:
    """The row count each layer's expert dispatch runs at for ``tokens``
    (b, s): (n_layers,), each a rung of ops/grouped_matmul.py
    ``dispatch_rungs``, the smallest that holds the layer's held rows."""
    return sown_by_layer(model, params, tokens, "moe", "dispatch_rows")
