"""How far the busiest expert of a program call stands above the mean:
the median over the traced calls (``traced_calls``) of
``expert_load_max`` (the most tokens one expert received in any expert
layer of the call) over the mean load ``tokens_routed x
num_experts_per_tok / n_routed_experts``.  1 is a perfectly even
routing; a decode call of 32 tokens over 64 experts reads 2 at best
(an expert holds whole tokens).  No call with counters: ``None``."""

import statistics

import traced_calls


def read(ctx):
    cfg = ctx.config
    per_token = int(cfg["num_experts_per_tok"]) / int(cfg["n_routed_experts"])
    ratios = [int(c["expert_load_max"]) / (int(c["tokens_routed"]) * per_token)
              for c in traced_calls.calls(ctx.facts) if int(c["tokens_routed"])]
    return statistics.median(ratios) if ratios else None
