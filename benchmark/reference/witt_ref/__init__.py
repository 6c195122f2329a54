"""The plain reference of the benchmark's configurations: a copy, taken
at PR 25, of the repo's oracle discrete-event simulator (a port of the
upstream Java DES: one priority queue, java.util.Random, per-message
latency sampling) with the Handel and GSFSignature protocols on it.

Copied from wittgenstein_tpu/{core,oracle,utils} and
wittgenstein_tpu/protocols/{handel,gsf,_aggregation}.py, unchanged
except that what the two protocols do not need is cut (the batched
protocol registry, p2p and blockchain nodes, runners, statistics).  It
imports nothing of the program, so that a PR that changes the program
cannot change what its results are held against.
"""
