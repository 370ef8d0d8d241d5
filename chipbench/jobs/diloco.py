"""diloco: streaming DiLoCo on a lone replica: byte-balanced fragments, the
quantized (fp8, Pallas) outer sync launched ``fragment_sync_delay`` steps
before it is applied. A unit is one round of ``sync_every`` inner steps, in
which every fragment syncs once."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from chipbench import harness


class Job:
    def __init__(self, run, system, params, spans) -> None:
        import jax
        import optax

        from torchft_tpu.local_sgd import DiLoCo

        traffic = run.traffic
        if traffic["quantize"] != "fp8":
            raise ValueError("the diloco job runs the fp8 outer sync")
        if int(traffic["steps_per_unit"]) != int(traffic["sync_every"]):
            raise ValueError("a unit must be one whole round: steps_per_unit == sync_every")
        self.system = system
        self.n_fragments = int(traffic["n_fragments"])
        fragment_fn = harness.balanced_fragments(params, self.n_fragments)
        self.plane = harness.Plane(
            "chipbench_diloco", timeout=float(traffic["manager_timeout_s"]),
            use_async_quorum=False,
        )
        outer = traffic["outer"]
        self.algo = DiLoCo(
            self.plane.manager,
            inner_tx=system.tx,
            outer_tx=optax.sgd(
                outer["learning_rate"], momentum=outer["momentum"],
                nesterov=outer["nesterov"],
            ),
            params=params,
            sync_every=int(traffic["sync_every"]),
            n_fragments=self.n_fragments,
            fragment_fn=fragment_fn,
            should_quantize=True,
            fragment_sync_delay=int(traffic["fragment_sync_delay"]),
        )
        sizes = [leaf.size for leaf in jax.tree_util.tree_leaves(params)]
        self.fragment_elements = [
            sum(sizes[i] for i in part) for part in fragment_fn(len(sizes))
        ]
        self._step = self.algo.make_step_fn(system.loss_fn)
        self.syncs: List[bool] = []

    def step(self, i: int):
        loss, committed = self._step(self.system.tokens(i))
        self.syncs.append(bool(committed))
        return loss

    def live_state(self):
        return self.algo.params

    def check(self, warm_steps: int, steps: int, units: int) -> Tuple[int, List[str]]:
        committed = sum(self.syncs[warm_steps:])
        want = self.n_fragments * units
        problems = []
        if committed != want:
            problems.append(f"{committed} fragment syncs committed in {units} round(s), not {want}")
        if self.plane.manager.current_step() != sum(self.syncs):
            problems.append("the manager's step is not the number of committed syncs")
        # A step fails when its fragment sync was due and did not commit.
        return max(0, want - committed), problems

    def observations(self) -> Dict[str, Any]:
        return {
            "fragments": self.n_fragments,
            "fragment_elements": self.fragment_elements,
        }

    def close(self) -> None:
        self.plane.shutdown()


def run(run) -> Dict[str, Any]:
    return harness.run_one_process(run, Job)
