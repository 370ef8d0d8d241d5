"""plain: the donated fused train step with no manager: the baseline every
FT number is read against, and the cell in which every FT layer is bypassed."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from chipbench import harness


class Job:
    def __init__(self, run, system, params, spans) -> None:
        import jax
        import optax

        tx, loss_fn = system.tx, system.loss_fn

        def plain(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        # The step as a user writes it: state donated.
        self._step = jax.jit(plain, donate_argnums=(0, 1))
        self.system = system
        self.params, self.opt_state = params, tx.init(params)

    def step(self, i: int):
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, self.system.tokens(i)
        )
        return loss

    def live_state(self):
        return self.params

    def check(self, warm_steps: int, steps: int, units: int) -> Tuple[int, List[str]]:
        return 0, []

    def observations(self) -> Dict[str, Any]:
        return {}

    def close(self) -> None:
        pass


def run(run) -> Dict[str, Any]:
    return harness.run_one_process(run, Job)
