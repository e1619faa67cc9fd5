"""The comparison that decides ``correct``: what the timed path served
against the plain reference, on a sample of the window's answers.

Each number is a widest gap over the sample:

``score_gap``         (classify) the served answer's score against the
                      reference's score of the same answer, in log-odds:
                      the program's ``log(conf / (1 - conf))`` beside the
                      reference's ``z[pred] - logsumexp(z[others])``, at
                      the exit that answered and, for an offloaded sample,
                      also at the cloud's final head (where the edge did
                      not answer, its confidence is held against the
                      reference's top score). A wrong answer reads about
                      twice its margin; a right one the error of its
                      confidence.
``served_logit_gap``  (decode) how far each served token's reference
                      logit lies below the reference's best, at the head
                      that served it (an exit, or the final head after an
                      offload).

The control puts the reference, computed in a lower precision, in the
program's place: its answer is the argmax of its own logits and its
confidence their softmax maximum.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np


def log_odds(conf) -> np.ndarray:
    c = np.clip(np.asarray(conf, np.float64), 1e-12, 1 - 1e-12)
    return np.log(c / (1 - c))


def softmax_max(logits) -> np.ndarray:
    z = np.asarray(logits, np.float64)
    z = z - z.max(-1, keepdims=True)
    return 1.0 / np.exp(z).sum(-1)


def score(logits, answer) -> np.ndarray:
    """Log-odds of ``answer`` under ``logits``: its logit less the
    log-sum-exp of the others (the log-odds of the softmax maximum when
    ``answer`` is the argmax)."""
    z = np.asarray(logits, np.float64)
    a = np.asarray(answer)[..., None]
    mine = np.take_along_axis(z, a, -1)[..., 0]
    others = np.where(np.arange(z.shape[-1]) == a, -np.inf, z)
    top = others.max(-1, keepdims=True)
    return mine - (top[..., 0] + np.log(np.exp(others - top).sum(-1)))


def served_gap(ref_logits, served) -> float:
    """Widest gap of the served ids' reference logits below the best."""
    ref = np.asarray(ref_logits, np.float64)
    got = np.take_along_axis(ref, np.asarray(served)[..., None], -1)[..., 0]
    return float(np.max(ref.max(-1) - got)) if ref.size else 0.0


def classify_numbers(ref_exits, ref_final, arms, exited, conf_edge,
                     conf_cloud, preds) -> Dict[str, float]:
    """Numbers for served classify answers.

    ``ref_exits`` (n, L, C) and ``ref_final`` (n, C) are reference logits;
    ``arms``, ``exited``, ``preds`` what the program chose and answered;
    ``conf_edge`` its confidence at the chosen exit and ``conf_cloud`` at
    the final head (NaN where the sample exited)."""
    n = len(arms)
    exited = np.asarray(exited, bool)
    preds = np.asarray(preds)
    edge = ref_exits[np.arange(n), arms]
    ref_edge = np.where(exited, score(edge, preds),
                        log_odds(softmax_max(edge)))
    gap = np.abs(log_odds(conf_edge) - ref_edge)
    off = ~exited
    gap_cloud = np.abs(log_odds(np.asarray(conf_cloud)[off])
                       - score(ref_final[off], preds[off]))
    return {"score_gap": float(max(gap.max(initial=0.0),
                                   gap_cloud.max(initial=0.0)))}


def classify_control(ref_exits, ref_final, ctl_exits, ctl_final, arms,
                     exited) -> Dict[str, float]:
    """The same numbers with the control's logits in the program's place,
    on the program's own choice of exit and offload."""
    n = len(arms)
    ctl_edge = ctl_exits[np.arange(n), arms]
    ctl_served = np.where(np.asarray(exited)[:, None], ctl_edge, ctl_final)
    return classify_numbers(ref_exits, ref_final, arms, exited,
                            softmax_max(ctl_edge), softmax_max(ctl_final),
                            np.argmax(ctl_served, -1))


def decode_layout(prompt, tok0, gen, depths, offloaded, num_layers: int):
    """Reference inputs of one served sequence.

    Returns (tokens (S+T,), valid (L, S+T), out_pos (T+1,),
    head_layer (T+1,), served (T+1,)): the prompt and every generated
    token but the last as input; keys valid at every layer for the
    prompt and, for generated position S+t, at layers <= depth[t] unless
    the token was offloaded; the prefill's answer from the final head at
    position S-1, and token t from the exit at its depth or, after an
    offload or at the last layer, from the final head (``L``)."""
    S, T, L = len(prompt), len(gen), num_layers
    tokens = np.concatenate([prompt, [tok0], gen[:-1]]).astype(np.int32)
    valid = np.ones((L, S + T), bool)
    layer = np.arange(L)[:, None]
    valid[:, S:] = (layer <= np.asarray(depths)[None, :]) \
        | np.asarray(offloaded, bool)[None, :]
    final = np.asarray(offloaded, bool) | (np.asarray(depths) == L - 1)
    head = np.concatenate([[L], np.where(final, L, depths)]).astype(np.int32)
    out_pos = np.arange(S - 1, S + T, dtype=np.int32)
    served = np.concatenate([[tok0], gen]).astype(np.int32)
    return tokens, valid, out_pos, head, served


def decode_numbers(ref_logits, served, ctl_logits: Optional[np.ndarray]
                   = None) -> Dict[str, float]:
    """``served_logit_gap`` of the served tokens, or with ``ctl_logits``
    of the tokens the control puts first."""
    if ctl_logits is not None:
        served = np.argmax(ctl_logits, -1)
    return {"served_logit_gap": served_gap(ref_logits, served)}
