"""ftddp: a lone replica through ``Optimizer.make_step_fn``: quorum and commit
vote every step. Since PR 60 the step votes first and then updates its state in
place (``params`` and ``opt_state`` donated), so ONE copy of the state, 6 bytes
a parameter (two from PR 32 to PR 59: the committed one, the history ring's one
version at depth 0, and a speculative one). What a user runs when the fleet has
shrunk to one group."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from chipbench import harness


class Job:
    def __init__(self, run, system, params, spans) -> None:
        from torchft_tpu.optim import Optimizer

        self.system = system
        self.plane = harness.Plane(
            "chipbench_ftddp", timeout=float(run.traffic["manager_timeout_s"])
        )
        self.opt = Optimizer(self.plane.manager, system.tx, params)
        self._step = self.opt.make_step_fn(system.loss_fn)
        self.commits: List[bool] = []

    def step(self, i: int):
        loss, committed = self._step(self.system.tokens(i))
        self.commits.append(bool(committed))
        return loss

    def live_state(self):
        return self.opt.params

    def check(self, warm_steps: int, steps: int, units: int) -> Tuple[int, List[str]]:
        mine = self.commits[warm_steps:]
        failed = sum(1 for c in mine if not c)
        problems = []
        if failed or not all(self.commits[:warm_steps]):
            problems.append(f"{failed} step(s) of the window did not commit")
        if self.plane.manager.current_step() != len(self.commits):
            problems.append(
                f"manager step {self.plane.manager.current_step()} after "
                f"{len(self.commits)} steps"
            )
        return failed, problems

    def observations(self) -> Dict[str, Any]:
        return {}

    def close(self) -> None:
        self.plane.shutdown()


def run(run) -> Dict[str, Any]:
    return harness.run_one_process(run, Job)
