"""`session_record_lost_events`: what the session's record says it lacks
(`driver/record_summary`) — Σ over its sources of recorded − delivered −
recovered, never less than the source's own `dropped`, plus the set-up spans
the aggregator evicted. Must read 0: the guard of the eight readers of the
record (`fit_to_loop_s` … `program_compiles_in_window`), whose `None` then
means the program has no such span and not that the record lost it."""

LAYER = "Launch"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(facts):
    from benchmarks.harness import session_record

    return session_record.lost_events(facts)
