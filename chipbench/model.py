"""From a configuration file to the system under test: the program's own
model (built by the configuration's architecture file), loss and optimizer,
and seeded inputs.

The benchmark brings the sizes and the seed, the program the code.
"""

from __future__ import annotations

from types import ModuleType
from typing import Any, Dict


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

    def __init__(
        self, config: Dict[str, Any], architecture: ModuleType,
        traffic: Dict[str, Any], seed: int,
    ) -> None:
        import jax
        import jax.numpy as jnp

        self.config, self.architecture = config, architecture
        self.traffic, self.seed = traffic, seed
        self.batch, self.seq = int(traffic["batch"]), int(traffic["seq"])
        self.tokens_per_step = self.batch * self.seq
        self.model = architecture.build(config, self.seq)
        self.tx = make_optimizer(config)
        # --seed runs to a little over 2**31: fold the high bit in instead of
        # handing PRNGKey a number that int32 cannot hold.
        base = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
        self._weights_key, self._tokens_key = jax.random.split(base)
        model, batch, seq, vocab = self.model, self.batch, self.seq, config["vocab_size"]

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
