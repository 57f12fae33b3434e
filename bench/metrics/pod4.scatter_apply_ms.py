"""`pod.scatter_apply_ms` in a cell that reports `mfu` and not
`round_ms`: the device time of the ops under the `pod_sync.scatter_apply`
named scope (the `expand_blocks` apply of every pod's payload), per chip
and round of the traced window."""
from pathlib import Path

from bench.harness import load_module

read = load_module(Path(__file__).with_name("pod.scatter_apply_ms.py")).read
