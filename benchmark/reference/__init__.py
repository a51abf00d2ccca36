"""The plain reference the benchmark's `correct` rests on.

Every module here except `oracle.py` is a byte-for-byte copy, taken at
commit 58b1ac2 (PR 21), of the pure-Python host path of
`distributed_plonk_tpu`: field, curve and pairing arithmetic, the Rescue
Merkle workload and its circuit, the KZG set-up and preprocess, the
TurboPlonk prover on `PythonBackend`, the transcript, the proof codec and
the verifier. It is kept here so that no later change to the program can
change what a served proof is held to. Nothing in this package imports jax
or the program, and it takes nothing the program made: keys, circuits and
public inputs are rebuilt here from the job spec alone.
`tests/benchmark/test_bench_reference.py` pins the copies to their
originals for as long as the originals stay in the tree.
"""
