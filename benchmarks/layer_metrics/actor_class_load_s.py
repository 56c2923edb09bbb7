"""`actor_class_load_s`: `worker/load_class` of the process that became the
`TrainWorker` — the actor's class fetched from the GCS and unpickled in the
new worker, which imports `ray_tpu.train` and with it JAX: the wait between
`raylet/worker_start`'s end and the constructor's slice, most of
`start_training_wait_s` where no split hides it."""

LAYER = "Launch"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(facts):
    from benchmarks.harness import session_record

    return session_record.actor_class_load_s(facts)
