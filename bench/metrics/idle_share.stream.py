"""Share of the traced window in which no operation ran on the device (%),
read for the stream cell by ``idle_share.trim``'s reader."""
from pathlib import Path

from bench import find

read = find.module(Path(__file__).resolve().parents[2], "metrics",
                   "idle_share.trim").read
