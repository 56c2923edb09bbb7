"""`peak_hbm_gib`: the fullest chip's peak: the larger of
`device.memory_stats()['peak_bytes_in_use']` after the window and what the
compiled step program needs on a device (`memory_analysis()`: arguments +
outputs - aliased + temporaries; this backend's allocator does not count a
program's temporaries)."""

LAYER = "Device"
UNIT = "GiB"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(facts):
    peak = facts['summary']['peak_bytes_in_use']
    return peak / 2.0 ** 30 if peak else None
