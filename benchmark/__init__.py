"""The benchmark of paddle_tpu: one command runs one cell once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that measures lives here, where a PR that claims a gain cannot
change it; from the program come only the system under test, its
counters and its kernels. See PERF.md.
"""
