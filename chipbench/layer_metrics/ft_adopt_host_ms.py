"""ft_adopt_host_ms: host milliseconds a step the FT step spends adopting the
committed state, from the capture's journal (``--trace 2``'s traced tail): the
seconds of the ``adopt`` events inside a root ``step`` event on the root's
thread, over the roots (``ft_dispatch_host_ms``'s selection). Its children in
the journal say where: ``state_swap``, ``history_promote`` and, when a version
leaves the ring there, ``history_evict``. None without a capture."""

from pathlib import Path

from chipbench import spec


def read(obs):
    dispatch = spec.load_module(Path(__file__).with_name("ft_dispatch_host_ms.py"))
    return dispatch.per_root_ms(obs, "adopt")
