"""The plain reference's verifying side, written for the benchmark from the
public specifications (FIPS 202, STROBE, merlin, the zcash point encoding,
the PLONK paper with jf-plonk's TurboPlonk gate and transcript schedule,
Rescue-Prime). It imports nothing of the program and nothing of
`benchmark/reference` (the frozen copy of the program's host prover), and
shares no arithmetic with either: affine curve arithmetic on Python ints,
keys by evaluating at the deployment's public test tau, and the opening
checks as equalities in G1 under that tau, so no pairing either."""
