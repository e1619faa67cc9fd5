"""The general traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes its inputs and arrivals from the
run's seed.

Classify requests are token rows of a synthetic review domain, a
vectorised copy of the repository's ``repro.data.synthetic.make_dataset``
(easy rows carry 5-8 class signal tokens, hard rows 2-3 and sometimes a
negation token; token 0 of every row is CLS). Open-loop arrivals come
from the process the mix names, one file each in ``bench/arrivals/``.
Decode prompts are token ids drawn uniformly from the vocabulary.
"""
from __future__ import annotations

import importlib.util
import os
from typing import Any, Dict

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def classify_tokens(domain: Dict[str, Any], n: int, seq_len: int,
                    rng: np.random.Generator) -> np.ndarray:
    """``n`` rows of ``seq_len`` token ids from a synthetic domain."""
    C = domain["num_classes"]
    ns = domain["num_signals"]
    cls = rng.integers(0, C, size=n)
    easy = rng.random(n) < domain["easy_frac"]
    toks = rng.integers(domain["distractor_lo"], domain["distractor_hi"],
                        size=(n, seq_len)).astype(np.int32)
    toks[:, 0] = domain["cls_token"]
    # signal tokens of class k: a block of ns ids, rotated per domain
    sig = (domain["signal_base"] + np.arange(C)[:, None] * ns
           + (np.arange(ns)[None, :] + domain["signal_rotate"]) % ns)
    k = np.where(easy, rng.integers(5, 9, size=n), rng.integers(2, 4, size=n))
    # distinct positions in 1..seq_len-1: the first slots of a permutation
    pos = np.argsort(rng.random((n, seq_len - 1)), axis=1)[:, :9] + 1
    picks = sig[cls[:, None], rng.integers(0, ns, size=(n, 9))]
    rows = np.arange(n)[:, None]
    use = np.arange(9)[None, :] < k[:, None]
    toks[rows.repeat(9, 1)[use], pos[use]] = picks[use]
    neg = ~easy & (rng.random(n) < 0.5)
    toks[np.nonzero(neg)[0], pos[neg, k[neg]]] = domain["negation_token"]
    return toks


def arrival_gaps(arrivals: Dict[str, Any], seconds: float,
                 rng: np.random.Generator, root: str = ROOT) -> np.ndarray:
    """Inter-arrival gaps (s) covering at least ``seconds``, from the mix's
    arrival process: ``gaps(arrivals, seconds, rng)`` of
    ``bench/arrivals/<process>.py``."""
    path = os.path.join(root, "bench", "arrivals",
                        f"{arrivals['process']}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_arrivals_{arrivals['process']}", path)
    if spec is None or not os.path.exists(path):
        raise ValueError(f"unknown arrival process {arrivals['process']!r}: "
                         f"no {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return np.asarray(mod.gaps(arrivals, seconds, rng), np.float64)


def decode_prompts(n: int, prompt_len: int, vocab: int,
                   rng: np.random.Generator) -> np.ndarray:
    return rng.integers(0, vocab, size=(n, prompt_len)).astype(np.int32)
