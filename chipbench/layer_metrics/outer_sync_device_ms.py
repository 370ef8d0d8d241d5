"""outer_sync_device_ms: device milliseconds one fragment sync's own programs
take: the traced window's device seconds of ``quantize_pseudograd`` and
``apply_outer`` (local_sgd.py's two codec programs, every op of them and not
the Pallas kernels alone, which ``codec_gbps`` reads) over the fragment syncs
in the window (fragments x rounds). Beside ``outer_sync_ms`` it says whether
what a sync adds to the loop is those programs or the inner step's slowdown
beside them."""

import re

SYNC_PROGRAM = re.compile(r"quantize_pseudograd|apply_outer")


def read(obs):
    trace, fragments = obs.get("trace"), obs.get("fragments")
    if not trace or not fragments or not obs.get("units"):
        return None
    seconds = sum(s for module, s in trace.get("modules", ()) if SYNC_PROGRAM.search(module))
    if not seconds:
        return None
    return 1e3 * seconds / (fragments * obs["units"])
