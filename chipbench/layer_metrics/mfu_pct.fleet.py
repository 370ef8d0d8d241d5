"""mfu_pct.fleet: mfu_pct over the four chips of the fleet (committed tokens of
both groups over the fleet's window)."""

from chipbench.spec import load_module
from pathlib import Path

read = load_module(Path(__file__).with_name("mfu_pct.py")).read
