"""`loop_expected_passes`: The pass a token is expected to exit at, Σ_t t ·
p̄_t, mean over the steps of the TIMED window — from the program's
`train/step_counters` events of kind `exit_distribution` (`exit_p1` …
`exit_p<T>`: the step's mean exit distribution over its valid tokens, float32
values the compiled step hands out beside its loss). Inside [1, passes];
1.875 where p is [1/2, 1/4, 1/8, 1/8] (a gate whose logit is 0); at birth
and through a window it is the seed's (1.03 and 2.26 on two: PERF.md §6,
PR 64). Nothing from a program that hands no such counters out of its
step."""

LAYER = "Model"
UNIT = "count"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"

EVENT = "train/step_counters"
KIND = "exit_distribution"


def read(facts):
    from statistics import fmean

    from benchmarks.harness import session_timeline

    rec = session_timeline.for_facts(facts)
    summary = facts["summary"]
    steps = [e["args"] for e in (rec["spans"].get(EVENT, ()) if rec else ())
             if e["args"].get("kind") == KIND
             and summary["t_window_wall"] <= e["args"].get("t_dispatch", 0.0)
             <= summary["t_end_wall"]]
    if not steps:
        return None
    passes = steps[0]["passes"]
    return fmean(sum((t + 1) * s[f"exit_p{t + 1}"][0] for t in range(passes))
                 for s in steps)
