"""Share of the coalescer thread's time spent launching and delivering
batches rather than waiting for requests, in percent: 100 x busy / (busy +
idle) from the program's ``serving_coalescer_busy_seconds`` and
``serving_coalescer_idle_seconds`` counters.  Nothing where the program
does not record them."""


def read(run):
    busy = run.counter("serving_coalescer_busy_seconds")
    idle = run.counter("serving_coalescer_idle_seconds")
    if busy + idle <= 0:
        return None
    return 100.0 * busy / (busy + idle)
