"""How ``correct`` is decided: the timed path's output against the plain
reference, once the window has closed.

* ``max_logit_gap``: a sample drawn from the seed of the requests that
  finished in the window (the one with the most served tokens always among
  them, and, where the mix shares prefixes, at least one request admitted
  on a prefix-cache hit and one admitted cold) is run through
  ``reference.py`` teacher-forced: each prompt followed by the tokens the
  program served. For every served token the reference gives its best
  logit and its logit for that token; the gap between the two is 0 where
  the program chose the reference's first token. The number is the widest
  gap over every served token of the sample.
* ``kv0_mismatch``: the keys and values that layer 0 wrote to the cache
  for every request in a slot when the window closed (prompt positions by
  the prefill programs, generated positions by the decode step at the
  served batch). They depend on a token and its position alone, through
  one normalisation and the integer core. The number is the share of
  those elements that differ from the reference's. On the CPU none
  differ; on a TPU v5e rounding alone moves about 15 %, and the exact
  product in the multiplier's place nearly 60 %. The served tokens alone
  cannot tell those two products apart: at these widths a random model
  carries either difference, and the program's rounding, to logits of
  about the same size.
* ``short_answers``: sampled requests that stopped short of ``max_new``
  (the traffic has no EOS, so every request runs to its length).
"""
from __future__ import annotations

import numpy as np

import reference


def choose(finished, seed: int, served_target: int, token_budget: int):
    """Indices into ``finished`` (dicts with prompt, output, hit)."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 1])
    cost = [len(r["prompt"]) + len(r["output"]) for r in finished]
    first = [int(np.argmax([len(r["output"]) for r in finished]))]
    for want in (lambda r: r["hit"], lambda r: not r["hit"]):
        pool = [i for i, r in enumerate(finished) if want(r)]
        if pool and not any(want(finished[i]) for i in first):
            first.append(int(rng.choice(pool)))
    picked, served, spent = [], 0, 0
    for i in first + list(rng.permutation(len(finished))):
        if i in picked:
            continue
        if picked and (served >= served_target
                       or spent + cost[i] > token_budget):
            continue
        picked.append(int(i))
        served += len(finished[i]["output"])
        spent += cost[i]
    return picked


def _served(req):
    return np.concatenate([req["prompt"], np.asarray(req["output"], np.int32)])


def _mismatch(k, v, ref_k, ref_v) -> float:
    """Share of the elements of (k, v) that differ from (ref_k, ref_v)."""
    differ = np.count_nonzero(k != ref_k) + np.count_nonzero(v != ref_v)
    return differ / (ref_k.size + ref_v.size)


def verify(weights, conf, sample, live):
    """The compared numbers of one run: list of (name, value, limit); the
    number of served tokens compared; and the shares of layer 0's keys
    and of its values that differ, apart. ``live``: layer 0's cache rows
    as the program wrote them (``tokens``, ``pos``, ``k``, ``v``)."""
    limits = conf["limits"]
    widest, served = 0.0, 0
    for req in sample:
        best, at, _ = reference.sequence_stats(weights, conf, _served(req))
        start = len(req["prompt"]) - 1
        widest = max(widest, float(np.max(best[start:] - at[start:, 0])))
        served += len(best) - start
    ref_k, ref_v = reference.layer0_kv(weights, conf, live["tokens"],
                                       live["pos"])
    kv0 = _mismatch(live["k"], live["v"], ref_k, ref_v)
    short = sum(1 for r in sample if len(r["output"]) != r["max_new"])
    split = {"k": float(np.mean(live["k"] != ref_k)),
             "v": float(np.mean(live["v"] != ref_v))}
    return [("max_logit_gap", widest, limits["max_logit_gap"]),
            ("kv0_mismatch", kv0, limits["kv0_mismatch"]),
            ("short_answers", short, 0)], served, split


def controls(conf):
    """The controls of a configuration, each the reference with the
    arguments that put it in the program's place: int4 codes, the step
    below int8; for the paper's multiplier also the exact product, the
    shortcut that would tempt a faster program."""
    out = {"int4": {"qmax": 7}}
    if conf["quant"]["core"] != "exact":
        out["exact_core"] = {"core": "exact"}
    return out


def control_numbers(weights, conf, sample, live):
    """Each control's readings on the run's own sample and live rows:
    ``max_logit_gap``, the widest gap in the configured reference of the
    token that the control ranks first at each served position, and
    ``kv0_mismatch``, the share of layer 0's cache that the control
    writes otherwise than the configured reference."""
    ref_k, ref_v = reference.layer0_kv(weights, conf, live["tokens"],
                                       live["pos"])
    out = {}
    for name, args in controls(conf).items():
        widest = 0.0
        for req in sample:
            seq = _served(req)
            start = len(req["prompt"]) - 1
            _, _, arg = reference.sequence_stats(weights, conf, seq, **args)
            best, at, _ = reference.sequence_stats(weights, conf, seq,
                                                   arg[:, None])
            widest = max(widest, float(np.max(best[start:] - at[start:, 0])))
        k, v = reference.layer0_kv(weights, conf, live["tokens"],
                                   live["pos"], **args)
        out[name] = {"max_logit_gap": widest,
                     "kv0_mismatch": _mismatch(k, v, ref_k, ref_v)}
    return out
