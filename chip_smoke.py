#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls (a real
``LighthouseServer`` + ``StoreServer`` + ``ProcessGroupNative`` + ``Manager``
on loopback, then ``Optimizer.make_step_fn`` and ``DiLoCo.make_step_fn`` on
a ``Llama``), at the full widths of ``CONFIGS["1b"]`` with random weights
from ``--seed``, and checks what comes out by the repo's own means. Any
failure is a non-zero exit: nothing is caught into a run that finishes.

    python chip_smoke.py            # one chip, one process; exits non-zero
                                    # when jax answers on anything but a TPU
    python chip_smoke.py --chips 4  # only what exists across chips: the
                                    # fsdp x tp mesh and ring attention in
                                    # one process, then two replica-group
                                    # processes of two chips each, one
                                    # SIGKILLed and live-healed
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                    # the same control flow at a tiny size
                                    # (Pallas in interpret mode); says which
                                    # platform it ran on and is never taken
                                    # unless asked for by name

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import hashlib
import json
import os
import re
import signal
import sys
import threading
import time
import traceback
from dataclasses import replace
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

# chip_smoke.py -> chipbench.harness -> torchft_tpu. Importing the harness
# touches no JAX backend (tests/test_chip_bringup.py holds it to that): the
# parent of ``--chips 4`` must stay off the chips its workers need.
from chipbench.harness import (  # noqa: E402
    LOOPBACK, CompileLedger, Plane, balanced_fragments,
)

GiB = 2**30

# Depth is the one cut (widths, vocabulary, sequence and dtype are
# CONFIGS["1b"]'s): 4 of 16 layers, for every phase, so that each FT path is
# held to the same plain reference. Sized by chipless compiles for a
# described v5e (scripts/hbm_probe.py) against the chip's 15.75 GiB, at
# batch 4: the plain donated step is 8.1 / 11.9 GiB at 4 / 8 layers. The FT
# paths hold more, and DiLoCo binds: its state is four parameter-sized trees
# (leaves, inner momentum, backups, outer momentum: 5.7 GiB at 4 layers,
# 6.6 at 6) beside a step that cannot donate (out 2.9 + temp 3.1 at 4
# layers) — 11.7 GiB predicted, 11.5 measured on the chip. At 6 layers that
# is 13.7 GiB before the ~1.5 GiB of finished sync payloads the manager's
# timed futures keep alive for their timeout: no margin, so 4. (FT-DDP alone
# holds ONE copy of the state since PR 60: the lone step votes first and is
# then given its state, and at depth 0 the history ring's one version IS
# that state. It held committed + speculative state before, under the
# plain phase's 5.79 GiB at 4 layers, measured, and 8.6 while the ring
# pinned one older version.)
SMOKE_LAYERS = 4
BATCH, SEQ = 4, 2048


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def smoke_config(rehearse: bool):
    """(LlamaConfig, batch, seq): CONFIGS["1b"] widths at SMOKE_LAYERS, or a
    tiny stand-in with the same switches for the rehearsal."""
    from torchft_tpu.models.llama import CONFIGS

    switches = dict(attention_impl="flash", scan_layers=True, remat="dots")
    if rehearse:
        return (
            replace(
                CONFIGS["tiny"], max_seq_len=64, loss_vocab_chunk=128, **switches
            ),
            2,
            64,
        )
    return (
        replace(
            CONFIGS["1b"],
            n_layers=SMOKE_LAYERS,
            max_seq_len=SEQ,
            loss_vocab_chunk=4096,
            **switches,
        ),
        BATCH,
        SEQ,
    )


def drill_config(rehearse: bool):
    """The smaller model of the one-chip thread drill: two groups share the
    chip there, and it checks the coordination plane, not FLOPs."""
    from torchft_tpu.models.llama import CONFIGS

    if rehearse:
        return replace(CONFIGS["tiny"], max_seq_len=32, scan_layers=True), 2, 32
    return (
        replace(
            CONFIGS["small"],
            max_seq_len=512,
            attention_impl="flash",
            scan_layers=True,
            loss_vocab_chunk=2048,
        ),
        4,
        512,
    )


def describe(config) -> str:
    import numpy as np

    return (
        f"dim {config.dim}, {config.n_heads} q / {config.n_kv_heads} kv heads "
        f"of {config.head_dim}, ffn {config.ffn_hidden}, vocab "
        f"{config.vocab_size}, {config.n_layers} layers, "
        f"{np.dtype(config.dtype).name}"
    )


# ---------------------------------------------------------------------------
# compile + cache accounting (jax.monitoring events)
# ---------------------------------------------------------------------------


class Phase:
    """Prints one phase's wall seconds, the compile seconds inside it and
    the device's peak bytes when it ends."""

    def __init__(self, name: str, ledger: CompileLedger, summary: dict) -> None:
        self.name, self.ledger, self.summary = name, ledger, summary

    def __enter__(self) -> "Phase":
        say(f"[{self.name}] start")
        self.t0 = time.monotonic()
        self.c0 = (self.ledger.compiles, self.ledger.compile_seconds)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            say(f"[{self.name}] FAILED: {exc_type.__name__}: {exc}")
            return
        import jax

        seconds = time.monotonic() - self.t0
        compiles = self.ledger.compiles - self.c0[0]
        compile_s = self.ledger.compile_seconds - self.c0[1]
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        self.summary[self.name] = {
            "seconds": round(seconds, 2),
            "compile_seconds": round(compile_s, 2),
            "compilations": compiles,
        }
        say(
            f"[{self.name}] ok: {seconds:.1f}s ({compile_s:.1f}s in {compiles} "
            f"compilations), device peak "
            + (f"{peak / GiB:.2f} GiB" if peak is not None else "not reported")
        )


def steady_window(ledger: CompileLedger, fn, n: int):
    """Runs ``fn(i)`` for i in range(n) and returns (results, seconds); a
    compilation inside the window is a failure (every shape was warmed)."""
    before = ledger.compiles
    t0 = time.monotonic()
    out = [fn(i) for i in range(n)]
    seconds = time.monotonic() - t0
    if ledger.compiles != before:
        raise AssertionError(
            f"{ledger.compiles - before} compilation(s) inside the stepping window"
        )
    return out, seconds


def release_device_memory() -> str:
    """Drops dead buffers between phases; says what is still on the device
    (a phase that leaks its state starves the next one of HBM)."""
    import jax

    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    stats = jax.devices()[0].memory_stats() or {}
    return (
        f"{live / 2**20:.1f} MiB in live arrays"
        + (f", {stats['bytes_in_use'] / GiB:.2f} GiB in use" if stats else "")
    )


# ---------------------------------------------------------------------------
# the FT plane on loopback
# ---------------------------------------------------------------------------


class DrillPlane:
    """One of SEVERAL replica groups on a lighthouse the caller owns: store,
    native process group, manager. ``chipbench.harness.Plane`` owns its
    lighthouse and so holds one group; the kill/heal drill needs two on one,
    which is all that differs."""

    def __init__(self, lighthouse_addr: str, replica_id: str, **manager_kwargs):
        from torchft_tpu.manager import Manager
        from torchft_tpu.parallel.native_pg import ProcessGroupNative
        from torchft_tpu.parallel.store import StoreClient, StoreServer

        self.store = StoreServer(f"{LOOPBACK}:0")
        self.pg = ProcessGroupNative(timeout=30.0)
        self.manager = Manager(
            pg=self.pg,
            store=StoreClient(self.store.address()),
            store_addr=self.store.address(),
            lighthouse_addr=lighthouse_addr,
            replica_id=replica_id,
            hostname=LOOPBACK,
            manager_bind=f"{LOOPBACK}:0",
            timeout=30.0,
            quorum_timeout=60.0,
            min_replica_size=1,
            **manager_kwargs,
        )

    def shutdown(self) -> None:
        self.manager.shutdown(wait=False)
        self.pg.shutdown()
        self.store.shutdown()


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def make_loss_fn(model):
    """``loss_fn(params, tokens)``: the fused linear+CE where the config
    chunks the vocabulary (no materialized logits), dense CE otherwise."""
    from torchft_tpu.models.llama import cross_entropy_loss

    def loss_fn(params, tokens):
        if model.config.loss_vocab_chunk:
            return model.apply(params, tokens[:, :-1], targets=tokens[:, 1:])
        return cross_entropy_loss(model.apply(params, tokens[:, :-1]), tokens[:, 1:])

    return loss_fn


def make_plain_step(tx, loss_fn):
    """The plain jitted SGD step as a user writes it — state donated: the
    reference the FT paths are compared with (also what
    tests/test_tpu_aot_compile.py and scripts/hbm_probe.py compile)."""
    import jax
    import optax

    def plain(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(plain, donate_argnums=(0, 1))


def seeded_tokens(seed: int, config, batch: int, seq: int):
    import jax

    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq + 1), 0, config.vocab_size
    )


def drill_tokens(args, config, batch: int, seq: int, step: int, group: int):
    """The kill/heal drills' data: keyed by step and group, so a restarted
    group re-aligns with the survivor by itself."""
    return seeded_tokens(args.seed + 7000 + 31 * step + group, config, batch, seq)


def state_digest(tree) -> list:
    """Per-leaf f32 |x| sums, computed on the device: equal digests after
    equal seeded steps say the two paths hold the same parameters without
    fetching them."""
    import jax

    return [float(x) for x in _leaf_abs_sums(jax.tree_util.tree_leaves(tree))]


def _leaf_abs_sums(leaves):
    # One jitted program for the process: the digest is taken three times
    # over the same tree shape.
    global _LEAF_ABS_SUMS
    if _LEAF_ABS_SUMS is None:
        import jax
        import jax.numpy as jnp

        _LEAF_ABS_SUMS = jax.jit(
            lambda ls: [jnp.sum(jnp.abs(l.astype(jnp.float32))) for l in ls]
        )
    return _LEAF_ABS_SUMS(leaves)


_LEAF_ABS_SUMS = None


class OneChip:
    """What the one-chip phases share. Each phase is its own method so that
    everything it put on the device dies with its frame: the next FT path
    needs the HBM (they are run one at a time for that reason)."""

    def __init__(self, args, ledger: CompileLedger, summary: dict) -> None:
        import jax
        import jax.numpy as jnp
        import optax

        from torchft_tpu.models.llama import Llama
        from torchft_tpu.utils.platform import on_tpu

        self.args, self.ledger, self.summary = args, ledger, summary
        self.config, self.batch, self.seq = smoke_config(args.rehearse)
        self.model = Llama(self.config)
        self.loss_fn = make_loss_fn(self.model)
        self.tx = optax.sgd(0.01, momentum=0.9)
        self.n_steps = 3 if args.rehearse else 4
        self.expect_kernel = on_tpu()
        self.init_params = jax.jit(
            lambda: self.model.init(
                jax.random.PRNGKey(args.seed),
                jnp.zeros((self.batch, self.seq), jnp.int32),
            )
        )
        # Set by plain_step(): the references the FT paths are held to.
        self.plain: dict = {}

    def phase(self, name: str) -> Phase:
        return Phase(name, self.ledger, self.summary)

    def batch_for(self, step: int):
        return seeded_tokens(
            self.args.seed + 1000 + step, self.config, self.batch, self.seq
        )

    def require_kernel(self, what: str, compiled) -> None:
        # interpret = not on_tpu() and the on_tpu() codec branches must have
        # taken the chip side: the compiled program holds the Mosaic call.
        present = "tpu_custom_call" in compiled.as_text()
        if self.expect_kernel and not present:
            raise AssertionError(f"{what}: no tpu_custom_call in the compiled program")
        say(f"  {what}: tpu_custom_call {'present' if present else 'absent (interpret mode)'}")

    def released(self) -> None:
        say(f"  released: {release_device_memory()}")

    # -- phases -------------------------------------------------------------

    def kernels(self) -> None:
        from torchft_tpu.ops import flash_attention, quantization

        with self.phase("kernels"):
            flash = flash_attention.verify_on_chip()
            say(
                f"  flash vs dense: fwd {flash['max_err']:.4f} bwd "
                f"{flash['max_err_bwd']:.4f} partial {flash['max_err_partial']:.4f} "
                f"zigzag {flash['max_err_zigzag']:.4f} ragged {flash['max_err_ragged']:.4f} "
                f"selected {flash['max_err_selected']:.4f} / {flash['max_err_selected_bwd']:.4f}"
            )
            say(
                "  the grid of needed pairs vs float32, no element apart from the "
                f"walk over every pair: {flash['max_err_listed']} backward "
                f"{flash['max_err_listed_bwd']}"
            )
            say(f"  block pairs by class of the schedule: {flash['classes']}")
            say(
                "  fused backward, q chunks a head (1: dq resident for the whole "
                f"sequence): {flash['bwd_q_chunks']}"
            )
            quant = quantization.verify_on_chip()
            say(
                "  codec vs host reference: "
                + ", ".join(
                    f"{w} {quant[f'{w}_max_err']:.4f} (host {quant[f'{w}_host_err']:.4f})"
                    for w in ("fp8", "int8", "int4")
                )
            )

    def plain_step(self) -> None:
        """The reference, two ways: the step as a user writes it (state
        donated), and the same math without donation — the program
        ``make_jit_fused_step`` builds, which is what the lone-replica FT-DDP
        step dispatches."""
        import jax
        import numpy as np

        from torchft_tpu.optim import make_jit_fused_step

        tx, loss_fn = self.tx, self.loss_fn

        def run(label: str, compiled, unpack) -> None:
            params = self.init_params()
            state = [params, tx.init(params)]
            del params

            def step(i: int) -> float:
                state[0], state[1], loss = unpack(
                    compiled(state[0], state[1], self.batch_for(i))
                )
                return float(loss)  # fetch: waits for the step

            losses, seconds = steady_window(self.ledger, step, self.n_steps)
            if not all(np.isfinite(losses)):
                raise AssertionError(f"{label} losses not finite: {losses}")
            say(f"  {label}: {self.n_steps} steps in {seconds:.2f}s, losses {losses}")
            self.plain[label] = {"losses": losses, "digest": state_digest(state[0])}

        with self.phase("plain-step"):
            params = self.init_params()
            opt_state = tx.init(params)
            n_params = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
            say(f"  {n_params / 1e6:.1f}M parameters")
            args = (params, opt_state, self.batch_for(0))
            t0 = time.monotonic()
            donated = make_plain_step(tx, loss_fn).lower(*args).compile()
            fused = make_jit_fused_step(tx, loss_fn).lower(*args).compile()
            say(f"  compiled both in {time.monotonic() - t0:.1f}s")
            del params, opt_state, args
            self.require_kernel("plain step", donated)
            run("donated", donated, lambda out: out)
            release_device_memory()
            run("not donated", fused, lambda out: (out[1], out[2], out[0]))
        self.summary["plain-step"].update(
            losses=self.plain["donated"]["losses"],
            losses_not_donated=self.plain["not donated"]["losses"],
        )

    def ft_ddp(self) -> None:
        from torchft_tpu.optim import Optimizer, make_jit_fused_step

        with self.phase("ft-ddp"):
            plane = Plane("smoke_ddp", 30.0)
            try:
                opt = Optimizer(plane.manager, self.tx, self.init_params())
                # The program make_step_fn's lone-replica path dispatches
                # (the one that is given its state), compiled here to read
                # its text (a compile-cache hit, like the step's own first
                # call; lowering gives nothing away).
                self.require_kernel(
                    "FT-DDP fused step",
                    make_jit_fused_step(self.tx, self.loss_fn, donate_state=True)
                    .lower(opt.params, opt.opt_state, self.batch_for(0))
                    .compile(),
                )
                step_fn = opt.make_step_fn(self.loss_fn)

                def step(i: int):
                    loss, committed = step_fn(self.batch_for(i))
                    return float(loss), bool(committed)

                results = [step(0)]  # warm: compiles
                more, seconds = steady_window(
                    self.ledger, lambda i: step(i + 1), self.n_steps - 1
                )
                results += more
                losses = [r[0] for r in results]
                commits = [r[1] for r in results]
                say(
                    f"  {self.n_steps} steps ({self.n_steps - 1} in the window: "
                    f"{seconds:.2f}s), committed {commits}, manager step "
                    f"{plane.manager.current_step()}\n  losses {losses}"
                )
                if not all(commits) or plane.manager.current_step() != self.n_steps:
                    raise AssertionError(f"FT-DDP steps did not all commit: {commits}")
                digest = state_digest(opt.params)
            finally:
                plane.shutdown()
            verdict = {}
            for label, ref in self.plain.items():
                dloss = max(abs(a - b) for a, b in zip(losses, ref["losses"]))
                bitwise = losses == ref["losses"] and digest == ref["digest"]
                verdict[label] = {"bitwise": bitwise, "max_abs_dloss": dloss}
                say(
                    f"  vs plain ({label}): max |dloss| {dloss:.3e}, parameter digests "
                    f"{'equal' if digest == ref['digest'] else 'differ'} -> "
                    + ("bitwise the same" if bitwise else "close, not bitwise")
                )
            # Since PR 60 the lone-replica step IS the fused program given
            # its state (the verdict first, then donation): bitwise the plain
            # step that is given its state too (and, on the chip at these
            # widths, the one that is not: my chip run, PR 60). Against the
            # other XLA may fuse the update differently; there the bound is
            # bf16's, 2^-8 of the loss.
            if not verdict["donated"]["bitwise"]:
                raise AssertionError(
                    "lone-replica FT-DDP is not bitwise the plain donated program"
                )
            tol = max(abs(l) for l in self.plain["donated"]["losses"]) * 2**-8
            for label, against in verdict.items():
                if against["max_abs_dloss"] > tol:
                    raise AssertionError(
                        f"FT-DDP losses differ from the {label} plain step by "
                        f"{against['max_abs_dloss']} > {tol}"
                    )
        self.summary["ft-ddp"].update(
            losses=losses, all_committed=True, vs_plain=verdict
        )

    def diloco(self) -> None:
        """Streaming DiLoCo: a cycle that compiles, then a steady one, every
        fragment sync through the quantized (fp8, Pallas) outer path."""
        import jax
        import numpy as np
        import optax

        from torchft_tpu.local_sgd import DiLoCo

        with self.phase("diloco"):
            n_fragments = 2 if self.args.rehearse else 4
            sync_every = 2 * n_fragments  # 2 inner steps per fragment sync
            plane = Plane("smoke_diloco", 30.0, use_async_quorum=False)
            try:
                params = self.init_params()
                algo = DiLoCo(
                    plane.manager,
                    inner_tx=self.tx,
                    outer_tx=optax.sgd(0.7, momentum=0.9, nesterov=True),
                    params=params,
                    sync_every=sync_every,
                    n_fragments=n_fragments,
                    fragment_fn=balanced_fragments(params, n_fragments),
                    should_quantize=True,
                    fragment_sync_delay=1,
                )
                del params
                step_fn = algo.make_step_fn(self.loss_fn)

                def inner_step(i: int):
                    loss, committed = step_fn(self.batch_for(i))
                    return float(loss), bool(committed)

                # Cycle 1 compiles the inner step and every fragment's codec
                # programs; cycle 2 is the steady window.
                t0 = time.monotonic()
                records = [inner_step(i) for i in range(sync_every)]
                warm_seconds = time.monotonic() - t0
                more, seconds = steady_window(
                    self.ledger, lambda i: inner_step(sync_every + i), sync_every
                )
                records += more
                losses = [r[0] for r in records]
                syncs = sum(r[1] for r in records)
                say(
                    f"  two cycles of {sync_every} inner steps ({n_fragments} "
                    f"fragments, fp8 wire, delay 1): first {warm_seconds:.1f}s "
                    f"with its compiles, second {seconds:.2f}s with none; "
                    f"{syncs} fragment syncs committed, manager step "
                    f"{plane.manager.current_step()}\n  losses {losses}"
                )
                if syncs != 2 * n_fragments or plane.manager.current_step() != syncs:
                    raise AssertionError(
                        f"DiLoCo committed {syncs}/{2 * n_fragments} fragment syncs"
                    )
                if not all(np.isfinite(losses)):
                    raise AssertionError(f"DiLoCo losses not finite: {losses}")
                # Until the first outer sync lands, the inner step is the
                # plain step on the same seeds.
                ref = self.plain["donated"]["losses"][0]
                if abs(losses[0] - ref) > abs(ref) * 2**-8:
                    raise AssertionError(
                        f"DiLoCo first inner loss {losses[0]} vs plain {ref}"
                    )
                # The codec programs of the cycles that just ran: the Pallas
                # kernels are on the main path.
                for frag in algo._fragments:
                    locals_ = [algo._leaves[j] for j in frag.leaf_indices]
                    payload, scales = jax.eval_shape(
                        frag._jit_quantize_pg, frag.backup, locals_
                    )
                    elems = sum(int(l.size) for l in locals_)
                    self.require_kernel(
                        f"fragment {frag._fragment_id} ({elems / 1e6:.0f}M elems) quantize",
                        frag._jit_quantize_pg.lower(frag.backup, locals_).compile(),
                    )
                    self.require_kernel(
                        f"fragment {frag._fragment_id} dequantize+outer step",
                        frag._jit_apply_outer.lower(
                            payload, scales, frag.backup, locals_, frag.outer_opt_state
                        ).compile(),
                    )
            finally:
                plane.shutdown()
        self.summary["diloco"].update(
            losses=losses, fragment_syncs_committed=syncs, quantized_wire="fp8"
        )


def one_chip(args, ledger: CompileLedger, summary: dict) -> None:
    from torchft_tpu import _native

    run = OneChip(args, ledger, summary)
    say(
        f"model: CONFIGS[\"1b\"] widths uncut — {describe(run.config)}; depth "
        f"{run.config.n_layers} of 16 (one chip's HBM, see SMOKE_LAYERS), batch "
        f"{run.batch} x seq {run.seq}, flash attention, scanned layers, dots "
        f"remat, fused CE chunk {run.config.loss_vocab_chunk}"
        if not args.rehearse
        else f"model: REHEARSAL stand-in — {describe(run.config)}, batch "
        f"{run.batch} x seq {run.seq}"
    )
    with run.phase("native-plane"):
        lib = _native.ensure_built()
        say(
            f"  libtpuft.so from tracked sources: {lib} "
            f"(digest {_native.source_digest()[:12]})"
        )
    if not args.rehearse:
        run.kernels()  # compiled, not interpreted
    run.plain_step()
    run.released()
    run.ft_ddp()
    run.released()
    run.diloco()
    run.released()
    with run.phase("kill-heal"):
        drill = kill_heal_threads(args)
    summary["kill-heal"].update(drill)


def kill_heal_threads(args) -> dict:
    """Two replica groups as threads of this one process sharing the chip:
    group 1 dies at a step boundary, comes back from a DIFFERENT seed (so
    equality is only reachable through a real heal) and live-heals from
    the survivor. Zero survivor steps may be lost and both groups must end
    bitwise identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.models.llama import Llama
    from torchft_tpu.optim import Optimizer

    config, batch, seq = drill_config(args.rehearse)
    model = Llama(config)
    loss_fn = make_loss_fn(model)
    # The rehearsal is a tier-1 test: half the steps show the same flow.
    n_steps, kill_at = (6, 2) if args.rehearse else (12, 5)
    init = jax.jit(
        lambda seed: model.init(
            jax.random.PRNGKey(seed), jnp.zeros((batch, seq), jnp.int32)
        )
    )
    lighthouse = LighthouseServer(
        bind=f"{LOOPBACK}:0", min_replicas=1, join_timeout_ms=2000
    )
    results: dict = {}
    failed = {0: 0, 1: 0}
    committed = {0: 0, 1: 0}
    heals = {"restarts": 0}

    class _Killed(Exception):
        pass

    def group_main(idx: int) -> None:
        for attempt in range(3):
            plane = DrillPlane(
                lighthouse.address(), f"smoke_drill_{idx}", heartbeat_interval=0.05
            )
            try:
                seed = args.seed if attempt == 0 else args.seed + 999
                opt = Optimizer(plane.manager, optax.sgd(0.05), init(seed))
                step_fn = opt.make_step_fn(loss_fn)
                while plane.manager.current_step() < n_steps:
                    step = plane.manager.current_step()
                    if idx == 1 and step == kill_at and attempt == 0:
                        raise _Killed()
                    _, ok = step_fn(drill_tokens(args, config, batch, seq, step, idx))
                    if ok:
                        committed[idx] += 1
                    else:
                        failed[idx] += 1
                results[idx] = [
                    np.asarray(l) for l in jax.tree_util.tree_leaves(opt.params)
                ]
                return
            except _Killed:
                heals["restarts"] += 1
                time.sleep(0.5)  # supervisor restart delay
            finally:
                plane.shutdown()
        raise RuntimeError(f"group {idx} exhausted restarts")

    errors: list = []

    def guarded(idx: int) -> None:
        try:
            group_main(idx)
        except BaseException as e:  # re-raised on the main thread below
            errors.append((idx, e))

    threads = [threading.Thread(target=guarded, args=(i,)) for i in range(2)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
    finally:
        lighthouse.shutdown()
    if errors:
        raise RuntimeError(f"drill group {errors[0][0]} failed") from errors[0][1]
    if any(t.is_alive() for t in threads) or set(results) != {0, 1}:
        raise AssertionError("kill/heal drill did not finish")
    identical = all(
        a.shape == b.shape and a.tobytes() == b.tobytes()
        for a, b in zip(results[0], results[1])
    )
    say(
        f"  {describe(config)}: group 1 killed at step {kill_at}, restarted "
        f"from another seed, healed; committed {committed}, survivor steps "
        f"lost {failed[0]}, parameters bitwise identical: {identical}"
    )
    if heals["restarts"] != 1 or failed[0] != 0 or not identical:
        raise AssertionError(
            f"kill/heal drill: restarts {heals['restarts']}, survivor lost "
            f"{failed[0]}, identical {identical}"
        )
    return {
        "survivor_steps_lost": failed[0],
        "bitwise_identical": identical,
        "committed": committed,
    }


# ---------------------------------------------------------------------------
# four chips: the in-slice mesh, then the replica axis across processes
# ---------------------------------------------------------------------------


def in_slice(args, ledger: CompileLedger, summary: dict) -> None:
    """One process owning four chips: one replica group on an fsdp=2 x tp=2
    mesh against the same seeded steps unsharded on one of the chips, then
    ring attention over sp=4 against single-device flash."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.models.llama import (
        Llama, apply_sharding_plan, sharding_plan,
    )
    from torchft_tpu.ops.flash_attention import flash_attention
    from torchft_tpu.ops.ring_attention import ring_attention_sharded
    from torchft_tpu.optim import Optimizer
    from torchft_tpu.parallel.mesh import ft_allreduce_sharded, ft_init_device_mesh
    from torchft_tpu.utils.platform import on_tpu

    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, jax sees {len(devices)}")
    config, batch, seq = smoke_config(args.rehearse)
    model = Llama(config)
    loss_fn = make_loss_fn(model)
    tx = optax.sgd(0.01, momentum=0.9)
    n_steps = 3
    say(f"model: {describe(config)}, batch {batch} x seq {seq}")

    def batch_for(step: int):
        return seeded_tokens(args.seed + 1000 + step, config, batch, seq)

    params0 = model.init(
        jax.random.PRNGKey(args.seed), jnp.zeros((batch, seq), jnp.int32)
    )

    with Phase("in-slice fsdp2xtp2", ledger, summary):
        plane = Plane("smoke_hsdp", 30.0)
        try:
            ft_mesh = ft_init_device_mesh(
                plane.manager, (2, 2), ("fsdp", "tp"), devices=devices[:4]
            )
            params = apply_sharding_plan(
                params0, ft_mesh.mesh, sharding_plan("fsdp", "tp")
            )
            opt = Optimizer(plane.manager, tx, params)
            del params

            def shard_bytes(tree) -> dict:
                held = {d.id: 0 for d in devices[:4]}
                for leaf in jax.tree_util.tree_leaves(tree):
                    for shard in leaf.addressable_shards:
                        held[shard.device.id] += shard.data.nbytes
                return held

            per_device = shard_bytes(opt.params)
            total = sum(l.nbytes for l in jax.tree_util.tree_leaves(opt.params))
            say(
                f"  parameter bytes: {total / GiB:.3f} GiB whole; shards per device "
                + ", ".join(f"{i}: {b / GiB:.3f}" for i, b in per_device.items())
                + " GiB"
            )
            # Really spread: no chip holds much more than a quarter (the
            # replicated norm scales are a few KiB).
            if max(per_device.values()) > 0.3 * total:
                raise AssertionError(f"parameters not spread over 4 chips: {per_device}")
            grad_fn = jax.jit(jax.value_and_grad(loss_fn))
            tokens_sharding = ft_mesh.sharding("fsdp", None)
            losses, commits = [], []
            with jax.set_mesh(ft_mesh.mesh):
                t0 = time.monotonic()
                text = (
                    grad_fn.lower(
                        opt.params, jax.device_put(batch_for(0), tokens_sharding)
                    )
                    .compile()
                    .as_text()
                )
                say(
                    f"  sharded grad step compiled in {time.monotonic() - t0:.1f}s: "
                    f"tpu_custom_call {'present' if 'tpu_custom_call' in text else 'absent'}, "
                    "collectives "
                    + ", ".join(
                        f"{op} x{len(re.findall(rf' {op}(-start)?[(]', text))}"
                        for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all")
                    )
                )
                if on_tpu() and "tpu_custom_call" not in text:
                    raise AssertionError("sharded step: flash kernel not in the program")
                for step in range(n_steps):
                    opt.begin_step()
                    loss, grads = grad_fn(
                        opt.params, jax.device_put(batch_for(step), tokens_sharding)
                    )
                    if step == 0:
                        say(
                            "  gradient shards per device (the compiler chose "
                            "their layout): "
                            + ", ".join(
                                f"{i}: {b / GiB:.3f}"
                                for i, b in shard_bytes(grads).items()
                            )
                            + " GiB"
                        )
                    if step == 1:
                        warmed = ledger.compiles  # step 0 compiled every program
                    commits.append(bool(opt.step(ft_allreduce_sharded(plane.manager, grads))))
                    losses.append(float(loss))
            say(f"  {n_steps} steps, committed {commits}, losses {losses}")
            if not all(commits):
                raise AssertionError(f"sharded steps did not all commit: {commits}")
            # The committed parameters must still lie where the plan put them:
            # an update that came back in another layout would recompile the
            # next step and could gather everything onto each chip.
            if shard_bytes(opt.params) != per_device or ledger.compiles != warmed:
                raise AssertionError(
                    f"after {n_steps} steps: parameter shards {shard_bytes(opt.params)} "
                    f"(were {per_device}), {ledger.compiles - warmed} compilation(s) "
                    "after the first step"
                )
            for d in devices[:4]:
                stats = d.memory_stats() or {}
                say(
                    f"  device {d.id}: in use {stats.get('bytes_in_use', 0) / GiB:.2f} GiB, "
                    f"peak {stats.get('peak_bytes_in_use', 0) / GiB:.2f} GiB"
                )
            del opt, grads
        finally:
            plane.shutdown()
    gc.collect()

    with Phase("one-chip reference", ledger, summary):
        step = make_plain_step(tx, loss_fn)
        p, o = params0, tx.init(params0)
        del params0
        ref_losses = []
        for i in range(n_steps):
            p, o, loss = step(p, o, batch_for(i))
            ref_losses.append(float(loss))
        del p, o
        diffs = [abs(a - b) for a, b in zip(losses, ref_losses)]
        # bf16 tolerance, stated: the sharded program reduces in another
        # order, so losses agree to 2^-8 relative, not bitwise.
        tol = max(abs(l) for l in ref_losses) * 2**-8
        say(
            f"  unsharded losses {ref_losses}; max |dloss| {max(diffs):.3e} "
            f"(bf16 tolerance {tol:.3e})"
        )
        if max(diffs) > tol:
            raise AssertionError(f"fsdp x tp losses differ from one chip by {max(diffs)}")
    summary["in-slice fsdp2xtp2"].update(losses=losses, ref_losses=ref_losses,
                                         max_abs_dloss=max(diffs))
    gc.collect()

    with Phase("ring attention sp=4", ledger, summary):
        from jax.sharding import Mesh

        sp_mesh = Mesh(np.array(devices[:4]), ("sp",))
        b, s, h, kv, d = (1, 64, 4, 2, 16) if args.rehearse else (2, 8192, 32, 8, 64)
        dtype = jnp.float32 if args.rehearse else jnp.bfloat16
        kq, kk, kvk = jax.random.split(jax.random.PRNGKey(args.seed + 5), 3)
        q = jax.random.normal(kq, (b, s, h, d), dtype)
        k = jax.random.normal(kk, (b, s, kv, d), dtype)
        v = jax.random.normal(kvk, (b, s, kv, d), dtype)
        ref = np.asarray(jax.jit(flash_attention)(q, k, v).astype(jnp.float32))
        errs = {}
        for use_flash in (False, True):
            out = ring_attention_sharded(q, k, v, sp_mesh, axis_name="sp", use_flash=use_flash)
            errs["pallas hops" if use_flash else "jnp hops"] = float(
                np.max(np.abs(np.asarray(out.astype(jnp.float32)) - ref))
            )
        say(
            f"  b{b} x s{s} x h{h}/kv{kv} x d{d} ({s // 4} per chip): max |ring - flash| "
            + ", ".join(f"{k_}: {e:.4f}" for k_, e in errs.items())
        )
        if max(errs.values()) > 0.05:
            raise AssertionError(f"ring attention over sp=4 differs from flash: {errs}")
    summary["ring attention sp=4"].update(max_abs_err=errs)


def replica_axis(args, summary: dict) -> None:
    """The parent stays off JAX and starts two replica-group processes of
    two chips each through ``launch.supervise`` (which hands each its own
    chips). Group 1 SIGKILLs itself mid-step; the supervisor restarts it;
    it live-heals its sharded state from the survivor. The lighthouse
    requires both groups (min_replicas=2), so the survivor waits for the
    restart instead of training ahead, and both must end with equal
    parameter digests."""
    import tempfile

    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.launch import supervise

    t0 = time.monotonic()
    say("[replica-axis] start")
    lighthouse = LighthouseServer(
        bind=f"{LOOPBACK}:0", min_replicas=2, join_timeout_ms=3000,
        heartbeat_timeout_ms=3000,
    )
    with tempfile.TemporaryDirectory(prefix="tpuft_smoke_") as out_dir:
        try:
            worker = [
                sys.executable, str(Path(__file__).resolve()), "--hsdp-worker", out_dir,
                "--seed", str(args.seed),
            ] + (["--rehearse"] if args.rehearse else [])
            rc = supervise(
                worker,
                num_replica_groups=2,
                lighthouse_addr=lighthouse.address(),
                relaunch_interval=1.0,
                max_restarts=1,  # the one SIGKILL; anything else is a failure
                extra_env={"TPUFT_LOG": os.environ.get("TPUFT_LOG", "warn")},
            )
        finally:
            lighthouse.shutdown()
            for log in sorted(Path(out_dir).glob("group*_attempt*.log")):
                say(f"  --- {log.name}")
                for line in log.read_text().splitlines():
                    if " INF tpuft] " not in line and "hugepage" not in line:
                        say(f"  | {line}")
        if rc != 0:
            raise AssertionError(f"launch.supervise returned {rc}")
        reports = [
            json.loads((Path(out_dir) / f"group{g}.json").read_text()) for g in range(2)
        ]
        killed = (Path(out_dir) / "killed").exists()
    seconds = time.monotonic() - t0
    for r in reports:
        say(
            f"  group {r['group']}: chips {r['chips']!r} ({r['n_devices']} devices), step {r['step']}, "
            f"attempt {r['attempt']}, healed {r['healed']}, digest {r['digest'][:16]}"
        )
        say("    wire stages, seconds (syncs): " + ", ".join(
            f"{stage} {stats['sum']:.2f} ({stats['count']})"
            for stage, stats in r["wire_stage_seconds"].items()
        ))
    equal = reports[0]["digest"] == reports[1]["digest"]
    same_step = reports[0]["step"] == reports[1]["step"]
    # On the CPU nothing is assigned (chips is None); on the chip the two
    # processes must have been handed disjoint sets.
    disjoint = args.rehearse or not (
        set(reports[0]["chips"].split(",")) & set(reports[1]["chips"].split(","))
    )
    if not (killed and equal and same_step and reports[1]["attempt"] == 1
            and reports[1]["healed"] and disjoint):
        raise AssertionError(f"replica-axis drill failed: {reports}, killed={killed}")
    say(
        f"[replica-axis] ok: {seconds:.1f}s — one SIGKILL, supervised restart, "
        f"live heal, digests equal at step {reports[0]['step']}"
    )
    summary["replica-axis"] = {
        "seconds": round(seconds, 2), "digests_equal": equal,
        "final_step": reports[0]["step"],
        "wire_stage_seconds": [r["wire_stage_seconds"] for r in reports],
    }


def hsdp_worker(args) -> None:
    """One replica-group process of the replica-axis drill: FT-HSDP steps on
    a mesh over the chips this process was given."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu import metrics
    from torchft_tpu.bootstrap import init_manager
    from torchft_tpu.models.llama import Llama, apply_sharding_plan, sharding_plan
    from torchft_tpu.optim import Optimizer
    from torchft_tpu.parallel.mesh import ft_allreduce_sharded, ft_init_device_mesh
    from torchft_tpu.parallel.native_pg import ProcessGroupNative
    from torchft_tpu.utils.platform import enable_compile_cache, require_tpu

    out_dir = Path(args.hsdp_worker)
    group = int(os.environ["REPLICA_GROUP_ID"])
    attempt = len(list(out_dir.glob(f"group{group}_attempt*.log")))
    # Each incarnation writes its own log, which the parent prints in order
    # afterwards: nothing of a worker's output depends on the stdio it
    # inherited, and the groups' lines do not interleave.
    log = open(out_dir / f"group{group}_attempt{attempt}.log", "w")
    os.dup2(log.fileno(), 1)
    os.dup2(log.fileno(), 2)
    if not args.rehearse:
        require_tpu()
    enable_compile_cache()
    devices = jax.devices()
    say(
        f"[group {group}] attempt {attempt}: {devices[0].platform} "
        f"{devices[0].device_kind} x{len(devices)}, ids {[d.id for d in devices]}, "
        f"TPU_VISIBLE_CHIPS={os.environ.get('TPU_VISIBLE_CHIPS')}"
    )
    n_local = 2 if len(devices) >= 2 else 1
    # The smoke's own model, widths uncut: the heal moves the real sharded
    # state (1.43 GiB of parameters over two chips) and every step's
    # gradients cross the replica axis at that size.
    config, batch, seq = smoke_config(args.rehearse)
    model = Llama(config)
    loss_fn = make_loss_fn(model)
    say(f"[group {group}] model: {describe(config)}, batch {batch} x seq {seq}")
    # Few steps: at this size every cross-group gradient sync is tens of
    # seconds of HOST work (ft_allreduce_sharded stages 2.9 GiB of bf16 —
    # the compiler leaves the gradients replicated over fsdp — and
    # manager.allreduce_pytree averages them in numpy), and four chips are
    # held meanwhile. One committed step, the kill, three steps after the
    # heal show the whole flow.
    n_steps, kill_at = 4, 1

    pg = ProcessGroupNative(timeout=120.0)
    manager, store = init_manager(
        pg, min_replica_size=2, replica_id=f"smoke_hsdp_{group}",
        timeout=120.0, quorum_timeout=120.0, heartbeat_interval=0.1,
        hostname=LOOPBACK, manager_bind=f"{LOOPBACK}:0",
    )
    try:
        ft_mesh = ft_init_device_mesh(
            manager, (n_local, 1), ("fsdp", "tp"), devices=devices[:n_local]
        )
        # The restarted process starts from another seed: the digests can
        # only agree at the end through a real heal.
        params = jax.jit(
            lambda: model.init(
                jax.random.PRNGKey(args.seed + 999 * attempt),
                jnp.zeros((batch, seq), jnp.int32),
            )
        )()
        params = apply_sharding_plan(params, ft_mesh.mesh, sharding_plan("fsdp", "tp"))
        opt = Optimizer(manager, optax.sgd(0.05), params)
        grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        first_commit_lands_at = None
        with jax.set_mesh(ft_mesh.mesh):
            while manager.current_step() < n_steps:
                step = manager.current_step()
                tokens = jax.device_put(
                    drill_tokens(args, config, batch, seq, step, group),
                    ft_mesh.sharding("fsdp", None),
                )
                opt.begin_step()
                loss, grads = grad_fn(opt.params, tokens)
                if group == 1 and step == kill_at and attempt == 0:
                    jax.block_until_ready(grads)
                    (out_dir / "killed").touch()
                    say(f"[group 1] SIGKILL at step {step}, mid-step")
                    os.kill(os.getpid(), signal.SIGKILL)
                t0 = time.monotonic()
                committed = opt.step(ft_allreduce_sharded(manager, grads))
                if first_commit_lands_at is None and committed:
                    first_commit_lands_at = manager.current_step()
                say(
                    f"[group {group}] step {step} loss {float(loss):.4f} "
                    f"participants {manager.num_participants()} committed "
                    f"{committed} (sync + commit {time.monotonic() - t0:.1f}s)"
                )
        digest = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(opt.params):
            digest.update(np.asarray(leaf).tobytes())
        (out_dir / f"group{group}.json").write_text(
            json.dumps(
                {
                    "group": group,
                    "chips": os.environ.get("TPU_VISIBLE_CHIPS"),
                    "n_devices": len(devices),
                    "step": manager.current_step(),
                    "attempt": attempt,
                    # A restarted process whose first commit lands past
                    # step 1 got there by healing, not by training.
                    "healed": bool(attempt and (first_commit_lands_at or 0) > 1),
                    "digest": digest.hexdigest(),
                    # Host seconds of this process's replica-axis syncs by
                    # stage (tpuft_wire_stage_seconds): the split of the
                    # tens of seconds a cross-group sync takes.
                    "wire_stage_seconds": {
                        stage: metrics.histogram_stats(
                            "tpuft_wire_stage_seconds", stage=stage
                        )
                        for stage in ("stage", "bucket", "ring", "average", "scatter")
                    },
                }
            )
        )
    finally:
        manager.shutdown(wait=False)
        pg.shutdown()
        if store is not None:
            store.shutdown()


# ---------------------------------------------------------------------------


def device_line() -> dict:
    import jax

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    say(f"device: {json.dumps(device)}")
    return device


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes on whatever platform the caller selected (CPU: "
        "JAX_PLATFORMS=cpu); never a chip result",
    )
    parser.add_argument("--hsdp-worker", metavar="DIR", help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.hsdp_worker:
        try:
            hsdp_worker(args)
        except BaseException:
            # A crashed worker must die now, not at interpreter exit: that
            # would wait out the manager's quorum thread (minutes, with
            # min_replicas=2 and the peer gone) while the chips are held.
            traceback.print_exc()
            sys.stdout.flush()
            os._exit(1)
        return

    from torchft_tpu.utils.platform import enable_compile_cache, require_tpu

    t_start = time.monotonic()
    summary: dict = {}
    if args.chips == 4:
        # Before this process touches JAX: the children need the chips.
        enable_compile_cache()  # exported: the workers inherit the directory
        replica_axis(args, summary)
    device = device_line()
    say(f"compile cache: {enable_compile_cache()}")
    if args.rehearse:
        say(f"REHEARSAL at tiny size on platform {device['platform']} — not a chip run")
    else:
        require_tpu()
    ledger = CompileLedger()
    if args.chips == 4:
        in_slice(args, ledger, summary)
    else:
        one_chip(args, ledger, summary)
    say(
        f"compilations: {ledger.compiles} taking {ledger.compile_seconds:.1f}s; "
        f"persistent cache hits {ledger.cache_hits}, misses {ledger.cache_misses}"
    )
    import jax

    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    say(
        "summary: "
        + json.dumps(
            {
                "rehearsal": args.rehearse,
                "chips": args.chips,
                "seed": args.seed,
                "total_seconds": round(time.monotonic() - t_start, 1),
                "compile_seconds": round(ledger.compile_seconds, 1),
                "compilations": ledger.compiles,
                "cache_hits": ledger.cache_hits,
                "cache_misses": ledger.cache_misses,
                "peak_bytes_in_use": peak,
                "phases": summary,
                "claim": None,
            }
        )
    )
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
