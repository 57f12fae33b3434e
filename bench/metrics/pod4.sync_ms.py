"""`pod.sync_ms` in a cell that reports `mfu` and not `round_ms`: the
device time of the ops under the `pod_sync.*` named scopes, per chip and
round of the traced window. On four pods it holds the all-gather of the
compact payloads."""
from pathlib import Path

from bench.harness import load_module

read = load_module(Path(__file__).with_name("pod.sync_ms.py")).read
