"""device_idle_pct.fleet: device_idle_pct as the mean of the fleet's four chips."""

from chipbench.spec import load_module
from pathlib import Path

read = load_module(Path(__file__).with_name("device_idle_pct.py")).read
