"""Plain float32 reference, the part that no architecture owns: the norm, the
rotary embedding, causal attention and the next-token loss of one sequence as
helpers a block may call, and the mean loss, its gradient and the first
optimizer update of a batch, given the architecture.

An architecture (``<dir>/architectures/<model_type>.py``, found by the
configuration's ``model_type``) writes its block after its published
description in straightforward ``jax.numpy`` and gives ``sequence_loss(params,
tokens, config, recompute)``: everything the training loss sums for one
sequence, from the system's parameter tree, whose names are the only thing it
takes from the code under test. No kernels, no cache, no scan, no chunked
vocabulary; every multiplication in float32 under
``jax.default_matmul_precision("highest")``, because a TPU otherwise runs a
float32 matmul in bf16 passes.

To bound memory at the real widths the loss is taken one sequence at a time
(``lax.map`` over the batch), and within a sequence of more than one block of
positions in blocks: attention in blocks of ``QUERY_BLOCK`` query positions,
each against the whole masked row of keys (heads x block x s float32 scores:
2 GiB at 32 x 2048 x 8192, where the whole matrix would be 8), and the
loss head in blocks of ``HEAD_BLOCK`` positions (block x vocabulary logits).
Softmax is by row, so the mathematics is that of the unblocked matrix and
nothing is approximated; in the gradient each block is recomputed
(``jax.checkpoint``). A sequence of at most one block runs the unblocked
program. The block sizes are constants of the reference, not keys of a
configuration (a rehearsal, which runs toy sequences, sets its own).

The update (``grad_sum`` + ``first_adamw_step``) is the float32 gradient of that
loss and AdamW's first step from zero moments written out by hand (Loshchilov &
Hutter, arXiv:1711.05101, with Adam's bias correction): after one step the
corrected moments are g and g*g, so the step is ``p - lr * (g / (|g| + eps) +
wd * p)``, stored in the configuration's parameter dtype. No optimizer library,
no moments: what the system's second loss is held to.
For the gradient alone each block of the model is recomputed in the backward
pass (``jax.checkpoint``), which changes memory and no number.

The update runs in one of two ways, the same mathematics in both, and
``whole_update_fits`` says which from the tree's size and the device's memory
alone. WHOLE (``make_loss_after_first_update``): one program from the system's
weights to the second loss, which holds a float32 copy, the summed gradient, one
sequence's gradient and the stepped weights of the WHOLE tree at once. BY PARTS
(``make_first_update_by_parts``, since PR 64): a part is the leaves of one
layer, or the leaves outside the layers; one program a part differentiates a
float32 copy of THAT part while every other leaf enters as the system stores it
and is cast where it is used, and returns the part stepped; the stepped tree is
put together from the parts and ``make_loss``'s program gives the second loss
on it. A chip's share whose whole update would not fit the chip gets its
``correct`` that way.
"""

from __future__ import annotations

import functools
import re
from types import ModuleType
from typing import Any, Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048
HEAD_BLOCK = 2048
# What the WHOLE update program holds at its fullest for every parameter of a
# bf16 tree: the system's weights (2 bytes), their float32 copy (4), the summed
# float32 gradient (4), one sequence's gradient inside ``grad_sum``'s scan (4)
# and the stepped weights (2), 16 in all, and the float32 activations of one
# sequence under ``recompute``: 16 to 18 by the records of PRs 36 to 62 (the
# configurations' ``reduced`` reasons; PERF.md section 7 has what the chip held
# in set-up by cell, PR 64). The upper end, so that the rule errs towards the
# parts.
WHOLE_UPDATE_BYTES_PER_PARAMETER = 18
_LAYER = re.compile(r"layer_\d+")  # a layer's subtree in the program's unstacked layout


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (s, heads, head_dim). Angle of pair i at position p: p * theta^(-2i/hd)."""
    s, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    first, second = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate(
        [first * cos - second * sin, first * sin + second * cos], axis=-1
    )


def _in_blocks(fn, block: int, *rows: jnp.ndarray) -> jnp.ndarray:
    """``fn(first, *rows)`` over ``rows`` (each (s, ...)) cut into blocks of
    ``block`` positions, one block at a time and recomputed in the gradient;
    ``first`` is the block's first position. One call where s <= block."""
    s = rows[0].shape[0]
    if s <= block:
        return fn(0, *rows)
    if s % block:
        raise ValueError(f"a sequence of {s} positions is not whole blocks of {block}")
    cut = [r.reshape(s // block, block, *r.shape[1:]) for r in rows]
    return jax.lax.map(
        lambda args: jax.checkpoint(fn)(*args), (jnp.arange(0, s, block), *cut)
    )


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Softmax attention of one sequence under the causal mask. q, k, v:
    (s, heads, head_dim), positions already encoded, shared key/value heads
    already repeated; scale head_dim^-0.5. Returns (s, heads, head_dim)."""
    s, _, hd = q.shape

    def rows(first, q_rows):
        scores = jnp.einsum("shk,thk->hst", q_rows, k) * hd**-0.5
        at = first + jnp.arange(q_rows.shape[0])
        causal = at[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hst,thk->shk", probs, v)

    return _in_blocks(rows, QUERY_BLOCK, q).reshape(q.shape)


def next_token_loss_sum(x: jnp.ndarray, head: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Sum over the positions of one sequence of the cross-entropy of
    ``softmax(x @ head)`` against ``targets``. x: (s, d); head: (d, vocab)."""

    def rows(_first, x_rows, target_rows):
        logp = jax.nn.log_softmax(x_rows @ head, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, target_rows[:, None], axis=-1))

    return jnp.sum(_in_blocks(rows, HEAD_BLOCK, x, targets))


def make_loss(architecture: ModuleType, config: Dict[str, Any]):
    """``loss(params, tokens)``: mean next-token cross-entropy of a batch
    ``tokens`` (b, s + 1), float32 at the highest matmul precision."""

    @jax.jit
    def loss(params, tokens):
        with jax.default_matmul_precision("highest"):
            sums = jax.lax.map(
                lambda seq: architecture.sequence_loss(params, seq, config), tokens
            )
        return jnp.sum(sums) / (tokens.shape[0] * (tokens.shape[1] - 1))

    return loss


def _grad_sum_of(loss_sum: Callable, params, tokens):
    """Gradient of ``loss_sum(params, sequence)`` summed over the sequences of
    ``tokens`` (n, s + 1), one sequence at a time."""
    grad = jax.grad(loss_sum)

    def add(total, seq):
        return jax.tree_util.tree_map(jnp.add, total, grad(params, seq)), None

    return jax.lax.scan(add, jax.tree_util.tree_map(jnp.zeros_like, params), tokens)[0]


def grad_sum(architecture: ModuleType, params, tokens, config: Dict[str, Any]):
    """Float32 gradient of the SUM of token losses of ``tokens`` (n, s + 1)
    with respect to float32 ``params``, one sequence at a time."""
    return _grad_sum_of(
        lambda p, seq: architecture.sequence_loss(p, seq, config, recompute=True),
        params, tokens,
    )


def first_adamw_step(params, grad_total, count, config: Dict[str, Any]):
    """Float32 ``params`` after ONE AdamW step from zero moments on the mean
    gradient ``grad_total / count``, computed in float32 and then rounded to
    the dtype the configuration keeps its parameters in (``run.dtype``). The
    rounding belongs to the configuration, not to the code under test: a step
    of 3e-4 is two or three bf16 units of a weight near 0.02, and without it
    the second loss differs by 1e-3 from any bf16 training, with it by 5e-5
    (my chip run, PR 24). The rounding is spelled ``lax.reduce_precision``
    before the cast: XLA may keep excess precision through a float32 -> bf16
    -> float32 pair of casts and did, where the caller's program let it see
    the pair (one sequence a batch: my chip run, PR 27), and then the weights
    were never rounded; ``reduce_precision`` it must honour."""
    opt = config["optimizer"]
    lr, wd, eps = float(opt["learning_rate"]), float(opt["weight_decay"]), float(opt["eps"])
    stored_as = jnp.dtype(config["run"]["dtype"])
    bits = jnp.finfo(stored_as)

    def leaf(p, g_sum):
        g = g_sum / count
        stepped = p - lr * (g / (jnp.abs(g) + eps) + wd * p)
        return jax.lax.reduce_precision(stepped, bits.nexp, bits.nmant).astype(stored_as)

    return jax.tree_util.tree_map(leaf, params, grad_total)


def make_loss_after_first_update(architecture: ModuleType, config: Dict[str, Any]):
    """``loss_after(params, first, then)``: the mean loss of the batch ``then``
    (b, s + 1) after one AdamW step on the mean loss of ``first`` (n, s + 1),
    which holds every sequence the step averages over. One program from the
    system's weights to a number: the float32 copy, the gradient and the
    stepped weights are its temporaries, so the reference leaves no array on
    the device and the run's ``peak_bytes_in_use`` is the job's own."""

    @jax.jit
    def loss_after(params, first, then):
        with jax.default_matmul_precision("highest"):
            p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
            count = first.shape[0] * (first.shape[1] - 1)
            stepped = first_adamw_step(
                p32, grad_sum(architecture, p32, first, config), count, config
            )
            sums = jax.lax.map(
                lambda seq: architecture.sequence_loss(stepped, seq, config), then
            )
        return jnp.sum(sums) / (then.shape[0] * (then.shape[1] - 1))

    return loss_after


# -- the update a part at a time ------------------------------------------------


def whole_update_fits(n_parameters: int, bytes_limit: int) -> bool:
    """Whether ``make_loss_after_first_update``'s one program fits a device of
    ``bytes_limit`` bytes for a tree of ``n_parameters``: a rule on sizes alone.
    A device that reports no limit (a CPU rehearsal) takes the whole."""
    return not bytes_limit or n_parameters * WHOLE_UPDATE_BYTES_PER_PARAMETER <= bytes_limit


def _stacked_layers(tree: Any) -> Dict[str, List[int]]:
    """Which layers each stacked subtree of the system's ``layers`` holds, in
    order along its leading axis: ``block`` every layer; ``block_<k>``, of a
    period of n kinds, the layers k, k + n, k + 2n, ... Empty for a tree that
    does not stack its layers under ``layers``."""
    stack = tree.get("layers") if isinstance(tree, dict) else None
    if not isinstance(stack, dict):
        return {}
    leading = lambda sub: jax.tree_util.tree_leaves(sub)[0].shape[0]
    if "block" in stack:
        return {"block": list(range(leading(stack["block"])))}
    period = len(stack)
    return {
        f"block_{k}": list(range(k, period * leading(stack[f"block_{k}"]), period))
        for k in range(period)
    }


def _unstacked(tree: Dict[str, Any]) -> Dict[str, Any]:
    """The system's tree (under ``params``) with its layers laid out one by one,
    ``layer_<i>``: the program's other layout, which every architecture file
    reads. A tree that does not stack its layers under ``layers`` is returned
    as it is. Slices: called while tracing, it copies nothing."""
    stacked = _stacked_layers(tree)
    if not stacked:
        return tree
    out = {name: sub for name, sub in tree.items() if name != "layers"}
    for name, layers in stacked.items():
        for at, layer in enumerate(layers):
            out[f"layer_{layer}"] = jax.tree_util.tree_map(lambda a: a[at], tree["layers"][name])
    return out


def _restacked(unstacked: Dict[str, Any], like: Dict[str, Any]) -> Dict[str, Any]:
    """The inverse of ``_unstacked``, in the layout ``like`` has. ``unstacked``
    is taken apart: leaf by leaf, a leaf's slices are let go before the next is
    stacked, so that at most one stacked leaf is held twice."""
    stacked_layers = _stacked_layers(like)
    if not stacked_layers:
        return unstacked
    out: Dict[str, Any] = {"layers": {}}
    for name, layers in stacked_layers.items():
        flat = [jax.tree_util.tree_flatten(unstacked.pop(f"layer_{layer}")) for layer in layers]
        slices, treedef = [leaves for leaves, _ in flat], flat[0][1]
        del flat
        stacked = []
        for at in range(treedef.num_leaves):
            stacked.append(jnp.stack([leaves[at] for leaves in slices]))
            for leaves in slices:
                leaves[at] = None
        out["layers"][name] = treedef.unflatten(stacked)
    out.update(unstacked)  # what is outside the layers
    return out


def _part_of(path: Sequence[Any]) -> str:
    """The part a leaf of an unstacked tree belongs to, by its path: a
    ``layer_<i>`` subtree, or an element of a list the tree keeps its layers in,
    is a part of its own; every other leaf is ``outside``."""
    head = path[0]
    name = str(getattr(head, "key", head))
    if _LAYER.fullmatch(name):
        return name
    if len(path) > 1 and isinstance(path[1], jax.tree_util.SequenceKey):
        return f"{name}[{path[1].idx}]"
    return "outside"


def parts_of(params: Dict[str, Any]) -> Dict[str, List[int]]:
    """The parts of the system's tree by name, each the positions of its leaves
    among the UNSTACKED tree's (``jax.tree_util.tree_leaves`` order). Shapes
    alone are read: nothing runs on a device."""
    shapes = jax.eval_shape(_unstacked, params["params"])
    parts: Dict[str, List[int]] = {}
    for at, (path, _) in enumerate(jax.tree_util.tree_flatten_with_path(shapes)[0]):
        parts.setdefault(_part_of(path), []).append(at)
    return parts


def make_part_step(architecture: ModuleType, config: Dict[str, Any], part: str):
    """``step(params, first)``: the leaves of ``part`` (a name of ``parts_of``)
    after one AdamW step on the mean loss of ``first`` (n, s + 1), in
    ``run.dtype``. One program a part. The float32 copy that ``jax.grad``
    differentiates is of that part alone; every other leaf enters the loss as
    the system stores it, and the architecture casts it where it uses it, so the
    loss is the whole program's to the bit and the gradient is the part's rows
    of the whole program's gradient."""

    @jax.jit
    def step(params, first):
        with jax.default_matmul_precision("highest"):
            leaves, treedef = jax.tree_util.tree_flatten(_unstacked(params["params"]))
            mine = parts_of(params)[part]

            def loss_sum(part32, seq):
                seen = list(leaves)
                for at, leaf in zip(mine, part32):
                    seen[at] = leaf
                view = {**params, "params": treedef.unflatten(seen)}
                return architecture.sequence_loss(view, seq, config, recompute=True)

            part32 = [leaves[at].astype(jnp.float32) for at in mine]
            count = first.shape[0] * (first.shape[1] - 1)
            return first_adamw_step(part32, _grad_sum_of(loss_sum, part32, first), count, config)

    return step


def assemble(params: Dict[str, Any], stepped: Dict[str, List[jnp.ndarray]]) -> Dict[str, Any]:
    """The tree in the layout ``params`` has whose parts are ``stepped`` (by
    name, each the part's leaves in ``parts_of``'s order). ``stepped`` is
    emptied: the tree is then the only holder of its arrays."""
    treedef = jax.tree_util.tree_structure(jax.eval_shape(_unstacked, params["params"]))
    leaves: List[Any] = [None] * treedef.num_leaves
    for name, places in parts_of(params).items():
        for at, leaf in zip(places, stepped.pop(name)):
            leaves[at] = leaf
    unstacked = treedef.unflatten(leaves)
    del leaves
    return {**params, "params": _restacked(unstacked, params["params"])}


def make_first_update_by_parts(architecture: ModuleType, config: Dict[str, Any]):
    """``update(params, first)``: the system's tree after one AdamW step on the
    mean loss of ``first`` (n, s + 1), every leaf in ``run.dtype``, in the
    layout ``params`` has: ``make_loss_after_first_update``'s stepped weights,
    made a part at a time. Alive at the worst instant: the system's weights, the
    parts stepped so far, and for ONE part its program's scratch (the part's
    float32 copy, two float32 gradients and its stepped leaves, beside one
    sequence's activations and the float32 casts a backward keeps). A part's
    program is let go before the next is loaded: on this runtime a loaded
    program's scratch stays reserved (my chip run, PR 64: the six programs of a
    1.36G share loaded together held 16.6 of the chip's 16.9 GB)."""

    def update(params, first):
        stepped = {}
        for name in parts_of(params):
            step = make_part_step(architecture, config, name)
            stepped[name] = jax.block_until_ready(step(params, first))
            del step
        return assemble(params, stepped)

    return update
