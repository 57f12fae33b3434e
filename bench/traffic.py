"""The benchmark's traffic: mixes, seeds and the token stream.

A traffic mix is a JSON file `bench/traffic/<mix>.json` of parameters. Its
`path` key names the program path it drives, `bench/paths/<path>.py`,
which turns the parameters into what it feeds the program, drawn from the
run's seed. The `pod` path (the pod-round step) reads its token stream
from `token_stream` below, a copy of the repository's synthetic LM stream
(`repro.data.synthetic`), kept here so that no later change to the program
moves the yardstick.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
TRAFFIC_DIR = BENCH / "traffic"


def load(mix: str, directory: Path | None = None) -> dict:
    """The parameters of traffic mix `mix`, whose `path` names a module
    under bench/paths."""
    p = json.loads(((directory or TRAFFIC_DIR) / f"{mix}.json").read_text())
    if not (BENCH / "paths" / f"{p.get('path')}.py").is_file():
        raise ValueError(f"traffic {mix}: no bench/paths/{p.get('path')}.py")
    return p


def sub_seeds(seed: int, n: int) -> list[int]:
    """`n` independent 31-bit seeds from a run seed of any size."""
    state = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(s) & 0x7FFFFFFF for s in state]


def token_stream(p: dict, seed: int, vocab: int, pods: int) -> np.ndarray:
    """int32 [rounds, pods, k, batch, seq + 1]: Zipfian unigrams with
    short-range copies (token[t] = token[t - lag] with prob `copy_prob`),
    the repository's synthetic LM stream. Every row of every round is a
    fresh draw."""
    rng = np.random.RandomState(sub_seeds(seed, 2)[1])
    shape = (int(p["stream_rounds"]), pods, int(p["k"]), int(p["batch"]),
             int(p["seq"]) + 1)
    rows, width = int(np.prod(shape[:-1])), shape[-1]
    w = 1.0 / np.arange(1, vocab + 1) ** float(p["zipf"])
    flat = rng.choice(vocab, size=rows * width, p=w / w.sum())
    flat = flat.reshape(rows, width)
    lag = int(p["copy_lag"])
    for t in range(lag, width):
        m = rng.rand(rows) < float(p["copy_prob"])
        flat[m, t] = flat[m, t - lag]
    return flat.astype(np.int32).reshape(shape)
