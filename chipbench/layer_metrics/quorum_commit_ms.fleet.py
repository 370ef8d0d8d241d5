"""quorum_commit_ms.fleet: quorum_commit_ms of the worst replica group."""

from chipbench.spec import load_module
from pathlib import Path

_of = load_module(Path(__file__).with_name("quorum_commit_ms.py")).of


def read(obs):
    values = [v for v in (_of(g) for g in obs.get("groups", [])) if v is not None]
    return max(values) if values else None
