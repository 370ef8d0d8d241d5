"""ft_idle_ms: milliseconds a step the device stood idle while the host was
inside the program's own spans: the traced window's idle seconds whose
innermost open host span is any ``tpuft::`` span (the root
``tpuft::optim::step`` included), over the window's steps. What is left under
the harness's ``chipbench/step`` is idle that the program has no span for."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("steps"):
        return None
    idle = sum(seconds for name, seconds in trace["gaps"] if name.startswith("tpuft::"))
    return 1e3 * idle / obs["steps"]
