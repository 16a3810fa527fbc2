"""How ``correct`` is decided: the timed path's output against the plain
reference, once the window has closed.

The reference is the configuration's architecture module
(``bench/arch/<architectures[0]>.py``): its ``sequence_stats`` and
``layer0_cache``, built on ``reference.py``'s numerics.

* ``max_logit_gap``: a sample drawn from the seed of the requests that
  finished in the window (the one with the most served tokens always among
  them, and, where the mix shares prefixes, at least one request admitted
  on a prefix-cache hit and one admitted cold) is run through the
  reference teacher-forced: each prompt followed by the tokens the
  program served. For every served token the reference gives its best
  logit and its logit for that token; the gap between the two is 0 where
  the program chose the reference's first token. The number is the widest
  gap over every served token of the sample.
* ``kv0_mismatch``: the leaves of layer 0's cache that the module names
  (a Llama's keys and values), as the program wrote them for every request
  in a slot when the window closed (prompt positions by the prefill
  programs, generated positions by the decode step at the served batch).
  They depend on a token and its position alone, through one
  normalisation and the integer core. The number is the share of their
  elements, over all the leaves together, that differ from the
  reference's. On the CPU none differ; on a TPU v5e rounding alone moves
  about 15 % of a Llama's, and the exact product in the multiplier's place
  nearly 60 %. The served tokens alone cannot tell those two products
  apart: at these widths a random model carries either difference, and
  the program's rounding, to logits of about the same size.
* ``short_answers``: sampled requests that stopped short of ``max_new``
  (the traffic has no EOS, so every request runs to its length).
"""
from __future__ import annotations

import numpy as np

import harness


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


def _mismatch(cache, ref) -> float:
    """Share of the elements of the leaves of ``cache`` that differ from
    ``ref``'s, over all the leaves together."""
    differ = sum(np.count_nonzero(cache[n] != ref[n]) for n in ref)
    return differ / sum(ref[n].size for n in ref)


def verify(weights, conf, sample, live):
    """The compared numbers of one run: list of (name, value, limit); the
    number of served tokens compared; and the share of each leaf of layer
    0's cache that differs, apart. ``live``: layer 0's cache rows as the
    program wrote them (``tokens``, ``pos``, and ``cache``, a dict of the
    leaves that the architecture module's ``cache0`` names)."""
    module = harness.arch_module(conf)
    limits = conf["limits"]
    widest, served = 0.0, 0
    for req in sample:
        best, at, _ = module.sequence_stats(weights, conf, _served(req))
        start = len(req["prompt"]) - 1
        widest = max(widest, float(np.max(best[start:] - at[start:, 0])))
        served += len(best) - start
    ref = module.layer0_cache(weights, conf, live["tokens"], live["pos"])
    kv0 = _mismatch(live["cache"], ref)
    short = sum(1 for r in sample if len(r["output"]) != r["max_new"])
    split = {n: float(np.mean(live["cache"][n] != ref[n])) for n in ref}
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
    module = harness.arch_module(conf)
    ref = module.layer0_cache(weights, conf, live["tokens"], live["pos"])
    out = {}
    for name, args in controls(conf).items():
        widest = 0.0
        for req in sample:
            seq = _served(req)
            start = len(req["prompt"]) - 1
            _, _, arg = module.sequence_stats(weights, conf, seq, **args)
            best, at, _ = module.sequence_stats(weights, conf, seq,
                                                arg[:, None])
            widest = max(widest, float(np.max(best[start:] - at[start:, 0])))
        got = module.layer0_cache(weights, conf, live["tokens"],
                                  live["pos"], **args)
        out[name] = {"max_logit_gap": widest,
                     "kv0_mismatch": _mismatch(got, ref)}
    return out
