"""95th percentile, over the requests the engine admitted in the traced
interval, of the time from each request's due time to the engine's
``admitted`` event (which the engine records once the request's prefill
has finished and its first token is sampled): queueing, scheduling and
the interleaved tail waves, without the delivery to the client."""
from bench.lib import readers


def read(rec):
    return readers.due_to_event_p95_ms(rec, "admitted")
