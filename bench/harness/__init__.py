"""The benchmark's harness: discovery by name, the client loop, metric
arithmetic, trace reduction and the correctness check. Nothing here is
specific to one configuration, traffic mix or per-layer metric; those live
in files of their own under ``bench/`` and are found by name."""
