"""The benchmark: `python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`. Everything the yardstick needs lives in this
directory; from the program it takes the system under test, its spans,
counters and kernel names. See PERF.md."""
