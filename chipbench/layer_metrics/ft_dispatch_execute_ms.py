"""ft_dispatch_execute_ms: milliseconds a dispatch the runtime's execute call
takes inside the FT step's ``update_dispatch`` span, from the capture's
``runtime`` table (``stop_capture()["runtime"]``: what the runtime did under
each of the program's spans, read from the same xplane, thread line and clock
as the span): among the names under ``tpuft::optim::update_dispatch`` that
hold ``Execute``, the one with most inclusive seconds, which is the outermost,
over the span's count. On the CPU that is ``PjRtCpuExecutable::Execute``; on
the chip ``PJRT_LoadedExecutable_Execute``, the C API's call into the TPU's
runtime (my chip run, PR 59: the runtime records on a line of its own, which
``stop_capture`` lays into its thread's; without that the only such name is
the 1 us ``PJRT_LoadedExecutable_Execute linkage`` at the call's door). With
``ft_dispatch_host_ms`` and the table's ``first_at_s`` it splits the dispatch
into before, inside and after the execute call. None without a capture, without the ``runtime`` key (a
program of before PR 59) and where no such name is under the span."""

SPAN = "tpuft::optim::update_dispatch"


def read(obs):
    runtime = (obs.get("capture") or {}).get("runtime") or {}
    span = runtime.get(SPAN)
    if not span or not span.get("count"):
        return None
    calls = [
        slot["seconds"] for name, slot in span["under"].items()
        if "Execute" in name and name != "other"
    ]
    if not calls:
        return None
    return 1e3 * max(calls) / span["count"]
