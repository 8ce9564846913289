"""Time one engine step stood off the CPU with work in hand: the self wall
time of the loop thread's WORKING spans (all of ``host["loop"]`` that is not in
``host["waits"]``) less the CPU time ``loop_cpu_ms`` reads, over the difference
of ``steps``: the wait for the interpreter, for a blocking write or for a core.
With ``loop_cpu_ms`` it adds up to the working spans' wall time a step. The
harness prints the traced run's reading. Nothing where the snapshot has no
``host``. Source: program_counter."""

from harness.host_counters import loop_offcpu_ms as read  # noqa: E402,F401
