"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the engine finished
in it, drawn from the seed and always holding the longest, is run through
the plain reference (``reference.py``) over prompt + served tokens. For
every served token the number read is its gap: how far the reference's
logit of that token lies below the reference's best logit at that
position. Greedy serving that computes what the configuration states
reads gaps at the level of rounding; the widest gap over the sample is
compared with the cell's limit in ``checks/<cell>.json``.

The control (``control.py``) reads the same gap for the token a lower
precision would put first at each of those positions.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def limits(cell: str, bench: str = HERE) -> dict:
    with open(os.path.join(bench, "checks", f"{cell}.json")) as f:
        return json.load(f)


def sample(finished: dict, seed: int, tokens: int, requests: int) -> list:
    """rids of finished requests to compare: the longest (prompt + served)
    first, then others in an order drawn from the seed, until the sample
    holds at least ``tokens`` served tokens and ``requests`` requests (or
    every finished one). Several requests, because a long greedy output
    of random weights falls into a loop whose logits any precision ranks
    alike. finished: rid -> (prompt, served)."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (len(finished[r][0])
                                       + len(finished[r][1]), -r))
    rng = np.random.default_rng([seed, 3])
    rest = [r for r in rng.permutation(rids).tolist() if r != longest]
    out, n = [longest], len(finished[longest][1])
    for r in rest:
        if n >= tokens and len(out) >= requests:
            break
        out.append(r)
        n += len(finished[r][1])
    return out


def served_rows(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose next-token logits chose the served tokens: the
    first served token follows the last prompt row."""
    return prompt_len - 1 + np.arange(n_served)


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """(R, V) reference logits and the token chosen at each row -> how far
    each chosen token's logit lies below the row's best."""
    ref = np.asarray(ref_logits, np.float64)
    picked = ref[np.arange(len(chosen)), np.asarray(chosen)]
    return ref.max(axis=1) - picked


def sequences(finished: dict, rids: list) -> tuple:
    """-> (token sequences prompt + served[:-1], logit rows, served
    tokens) for the sampled requests. The last served token is never fed
    back, so it is left off the sequence."""
    seqs, rows, served = [], [], []
    for r in rids:
        prompt, out = finished[r]
        seqs.append(np.concatenate([prompt, out[:-1]]).astype(np.int32))
        rows.append(served_rows(len(prompt), len(out)))
        served.append(np.asarray(out))
    return seqs, rows, served


def widest_gap(cfg, params, finished: dict, rids: list,
               act_bits: int = 8, kv_bits: int = 8) -> dict:
    """Widest gap of the served tokens of ``rids`` under the reference."""
    from reference import logits_at

    seqs, rows, served = sequences(finished, rids)
    ref = logits_at(cfg, params, seqs, rows, act_bits=act_bits,
                    kv_bits=kv_bits)
    g = np.concatenate([gaps(lg, s) for lg, s in zip(ref, served)])
    return {"max_gap": float(g.max()), "served_tokens": int(g.size),
            "requests": len(rids)}


def compare(cfg, params, finished: dict, rids: list,
            control: bool = False) -> dict:
    """The number that decides ``correct``, as ``max_gap``: the program's
    widest gap, or with ``control`` the control's in its place (and the
    program's beside it, as ``program``)."""
    if control:
        got = control_gap(cfg, params, finished, rids)
        return dict(got, max_gap=got["control"])
    return widest_gap(cfg, params, finished, rids)


def control_gap(cfg, params, finished: dict, rids: list,
                act_bits: int = 4) -> dict:
    """The control: the reference computed with activations at
    ``act_bits`` puts a token first at each served position; its gap under
    the reference at the configuration's 8 bits. Also returns the
    program's own widest gap over the same positions."""
    from reference import logits_at

    seqs, rows, served = sequences(finished, rids)
    ref = logits_at(cfg, params, seqs, rows)
    low = logits_at(cfg, params, seqs, rows, act_bits=act_bits)
    prog = np.concatenate([gaps(r, s) for r, s in zip(ref, served)])
    ctl = np.concatenate([gaps(r, lo.argmax(axis=1))
                          for r, lo in zip(ref, low)])
    return {"program": float(prog.max()), "control": float(ctl.max()),
            "served_tokens": int(prog.size), "requests": len(rids)}
