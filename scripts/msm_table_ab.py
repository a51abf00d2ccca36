"""Chip A/B of a commit's two tails (PR 36): the bucket scan and the finish
of a 16,416-point key, each program alone, ladder path (`finish`) against
window-table path (`finish_preweighted`), at B = 1, 5, 2, in one process.

    chiprun -- python scripts/msm_table_ab.py

Prints a line a path and batch (median seconds a call), checks that both
paths give the same points and that both equal the host oracle on a short
polynomial, times the table's build (first context of the process, then
a second), and writes chiprun_out/pr36/msm_micro.json. PERF.md sec. 5 has
the readings."""
import json, os, sys, time, random
sys.path.insert(0, os.getcwd())
import numpy as np
import jax, jax.numpy as jnp
from distributed_plonk_tpu import curve as C
from distributed_plonk_tpu.constants import R_MOD
from distributed_plonk_tpu.backend import msm_jax as MJ

out = {"device": str(jax.devices()[0].device_kind), "platform": jax.devices()[0].platform}
dev = jax.devices()[0]
def peak(): return dev.memory_stats().get("peak_bytes_in_use")
rng = random.Random(36)
pts = [C.g1_mul(C.G1_GEN, rng.randrange(1, R_MOD)) for _ in range(16)]
n = 16416
bases = [pts[i % 16] for i in range(n - 32)] + [None] * 32
W = 37

def timed(fn, *args, reps=5):
    r = fn(*args); jax.block_until_ready(r)
    ts = []
    for _ in range(reps):
        t = time.perf_counter(); r = fn(*args); jax.block_until_ready(r)
        ts.append(time.perf_counter() - t)
    return sorted(ts)[len(ts) // 2], r

def bench(ctx, tag):
    res = {}
    for B in (1, 5, 2):
        digits = jnp.stack([jnp.asarray(MJ.signed_digits7_of_scalars(
            [rng.randrange(R_MOD) for _ in range(n - 40)], n)) for _ in range(B)])
        g = MJ._group_size_batch(n, B, 7, signed=True, kernel="xla")
        fn = ctx._chunk_fn(n, g)
        if ctx._preweighted():
            args = (*ctx.table, ctx.point[2], digits)
        else:
            args = (*ctx.point, digits)
        t0 = time.perf_counter(); r = fn(*args); jax.block_until_ready(r); first = time.perf_counter() - t0
        scan_s, planes = timed(fn, *args)
        fin = ctx._finish_fn(B)
        t0 = time.perf_counter(); r = fin(*planes); jax.block_until_ready(r); ffirst = time.perf_counter() - t0
        fin_s, tot = timed(fin, *planes, reps=9)
        res[B] = {"group": g, "scan_s": scan_s, "finish_s": fin_s, "scan_first_s": first, "finish_first_s": ffirst,
                  "points": MJ._decode_totals(B, tot), "digits": digits}
        print(tag, "B", B, "scan %.4f finish %.4f (first %.1f / %.1f) peak %.3f GB" % (scan_s, fin_s, first, ffirst, peak() / 1e9), flush=True)
    return res

t = time.perf_counter(); lad_budget = MJ._TABLE_BYTES_BUDGET
MJ._TABLE_BYTES_BUDGET = 0
ladder = MJ.MsmContext(bases); jax.block_until_ready(ladder.point)
MJ._TABLE_BYTES_BUDGET = lad_budget
out["ladder_ctx_s"] = time.perf_counter() - t
out["peak_after_ladder_ctx"] = peak()
r_l = bench(ladder, "ladder")
out["peak_after_ladder"] = peak()
t = time.perf_counter(); table = MJ.MsmContext(bases); jax.block_until_ready(table.table)
out["table_ctx_first_s"] = time.perf_counter() - t
t = time.perf_counter(); table2 = MJ.MsmContext(bases); jax.block_until_ready(table2.table)
out["table_ctx_second_s"] = time.perf_counter() - t
del table2
out["table_shape"] = list(table.table[0].shape)
out["peak_after_table_ctx"] = peak()
r_t = {}
# same digits on both paths: reuse the ladder's
for B in (1, 5, 2):
    digits = r_l[B]["digits"]
    g = r_l[B]["group"]
    fn = table._chunk_fn(n, g)
    args = (*table.table, table.point[2], digits)
    t0 = time.perf_counter(); r = fn(*args); jax.block_until_ready(r); first = time.perf_counter() - t0
    scan_s, planes = timed(fn, *args)
    fin = table._finish_fn(B)
    t0 = time.perf_counter(); r = fin(*planes); jax.block_until_ready(r); ffirst = time.perf_counter() - t0
    fin_s, tot = timed(fin, *planes, reps=9)
    same = MJ._decode_totals(B, tot) == r_l[B]["points"]
    r_t[B] = {"scan_s": scan_s, "finish_s": fin_s, "scan_first_s": first, "finish_first_s": ffirst, "equal_to_ladder": same}
    print("table B", B, "scan %.4f finish %.4f (first %.1f / %.1f) equal %s peak %.3f GB" % (scan_s, fin_s, first, ffirst, same, peak() / 1e9), flush=True)
out["peak_after_table"] = peak()
# whole commits through the public surface, both contexts, against the host oracle on a short poly
sc = [[rng.randrange(R_MOD) for _ in range(24)] + [0, 1, R_MOD - 1], [0] * 5]
want = [C.g1_msm(bases[:len(s)], s) for s in sc]
out["oracle_equal"] = {"table": table.msm_many(sc) == want, "ladder": ladder.msm_many(sc) == want}
for B in (1, 5):
    hs = [[rng.randrange(R_MOD) for _ in range(n - 32)] for _ in range(B)]
    for ctx, tag in ((ladder, "ladder"), (table, "table")):
        ctx.msm_many(hs)
        t = time.perf_counter(); ctx.msm_many(hs); out["commit_%s_B%d_s" % (tag, B)] = time.perf_counter() - t
out["ladder"] = {B: {k: v for k, v in r.items() if k not in ("points", "digits")} for B, r in r_l.items()}
out["table"] = r_t
os.makedirs("chiprun_out/pr36", exist_ok=True)
json.dump(out, open("chiprun_out/pr36/msm_micro.json", "w"), indent=1)
print(json.dumps(out))
