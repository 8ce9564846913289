"""The part of ``contender_cpu_share`` that is the benchmark's own threads in
the same interpreter, in % of one core: the ``user`` role's CPU (the load
generator, the main thread, every thread that took no role) over ``wall_us``. A
LOWER bound of what a deployment, whose clients are other processes, would not
pay: the client's share of the poller's and the workers' CPU (completing a call,
handing a stream frame to the caller) is not in it, because no clock of this
host splits a thread's CPU by span. The harness prints the traced run's reading,
where the main thread also stops the profiler. Nothing where the snapshot has no
``host``. Source: program_counter."""

from harness.host_counters import client_cpu_share as read  # noqa: E402,F401
