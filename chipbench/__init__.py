"""The chip benchmark: one command runs one cell of ``BENCHMARK.json``.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that measures lives here: traffic generation, plain float32
references, the operation and byte counts, the table of peaks and the
reduction of profiler traces to metrics.  From the program under test the
benchmark takes only its public entry points (model, policy, train step)
and the names of its kernels in the trace.
"""
