"""From a configuration file to the system under test: the program's own
model, loss and optimizer, built from the file's keys, and seeded inputs.

Only names of the program are used here (``Llama``, ``LlamaConfig``,
``optax``): the benchmark brings the sizes and the seed, the program the code.
"""

from __future__ import annotations

from typing import Any, Dict


def llama_config(config: Dict[str, Any], seq: int):
    """The program's ``LlamaConfig`` for a configuration file as it is run."""
    import jax.numpy as jnp

    from torchft_tpu.models.llama import LlamaConfig

    if config["hidden_size"] != config["num_attention_heads"] * config["head_dim"]:
        raise ValueError("models/llama.py derives head_dim as hidden_size / heads")
    if config.get("sliding_window") is not None:
        raise ValueError("models/llama.py has no sliding window")
    run = config["run"]
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        dim=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        ffn_hidden=config["intermediate_size"],
        max_seq_len=seq,
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=jnp.dtype(run["dtype"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        attention_impl=run["attention_impl"],
        remat=run["remat"],
        loss_vocab_chunk=run["loss_vocab_chunk"],
        scan_layers=run["scan_layers"],
    )


def make_optimizer(config: Dict[str, Any]):
    """``optax.adamw`` from the file's ``optimizer`` group, its first moment in
    ``adam_mu_dtype`` (optax keeps the second in the parameters' dtype)."""
    import jax.numpy as jnp
    import optax

    spec = config["optimizer"]
    if spec["name"] != "adamw":
        raise ValueError(f"optimizer {spec['name']!r}: only adamw is defined")
    return optax.adamw(
        spec["learning_rate"], eps=spec["eps"], weight_decay=spec["weight_decay"],
        mu_dtype=jnp.dtype(config["adam_mu_dtype"]),
    )


class System:
    """What every job shares: model, loss, optimizer, seeded weights and
    tokens for one configuration under one traffic mix."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> None:
        import jax
        import jax.numpy as jnp

        from torchft_tpu.models.llama import Llama

        self.config, self.traffic, self.seed = config, traffic, seed
        self.batch, self.seq = int(traffic["batch"]), int(traffic["seq"])
        self.tokens_per_step = self.batch * self.seq
        self.llama = llama_config(config, self.seq)
        self.model = Llama(self.llama)
        self.tx = make_optimizer(config)
        # --seed runs to a little over 2**31: fold the high bit in instead of
        # handing PRNGKey a number that int32 cannot hold.
        base = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
        self._weights_key, self._tokens_key = jax.random.split(base)
        model, batch, seq, vocab = self.model, self.batch, self.seq, self.llama.vocab_size

        def loss_fn(params, tokens):
            # The fused linear + cross-entropy path: no materialised logits.
            return model.apply(params, tokens[:, :-1], targets=tokens[:, 1:])

        self.loss_fn = loss_fn
        # One jitted call makes every weight on the device, in the dtype it
        # is trained in.
        self._init = jax.jit(
            lambda key: model.init(key, jnp.zeros((batch, seq), jnp.int32))
        )
        self._tokens = jax.jit(
            lambda key, step, group: jax.random.randint(
                jax.random.fold_in(jax.random.fold_in(key, step), group),
                (batch, seq + 1), 0, vocab,
            )
        )

    def init_params(self):
        return self._init(self._weights_key)

    def tokens(self, step: int, group: int = 0):
        """The batch of ``step`` (and replica group): same seed, same tokens."""
        return self._tokens(self._tokens_key, step, group)
