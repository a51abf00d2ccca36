"""Batched device stage kernels for a jax-backend fleet worker.

The generic worker path runs the 4-step FFT stage kernels row by row
through the int-list backend API (fine for the python oracle backend, but
a jax worker would pay one device dispatch per row — hundreds of
dispatches per FFT1 frame). This module runs a whole FFT1/FFT2 frame as
ONE jitted launch over the (16, rows, len) limb panel, with the coset /
mid / inverse-coset twiddle scalings folded in as precomputed Montgomery
tables — and no host int conversion anywhere (wire bytes <-> limb panels
only).

Stage math matches worker._stage1_row/_stage2_row (the reference's
fft1/fft2 helpers, /root/reference/src/worker.rs:66-115) bit for bit.
"""

import numpy as np
import jax
import jax.numpy as jnp

from ..backend import ntt_jax
from ..backend import field_jax as FJ
from ..backend.field_jax import FR
from ..constants import R_MOD, FR_GENERATOR
from ..fields import fr_inv, fr_root_of_unity


class StageKernels:
    """Per-worker cache of twiddle tables + jitted panel kernels."""

    _TABLE_CAP = 8  # (n, mode, range) table sets kept resident

    def __init__(self):
        self._tables = {}

    @staticmethod
    @jax.jit
    def _panel_fn(v, pre, mid, post, core):
        """(16, B, L) canonical panel -> staged canonical panel. pre/mid/
        post are optional Montgomery scale tables (None-ness is static per
        trace); core is a shared stage-core table set
        (ntt_jax.NttPlan.core_consts), so the fleet panels run the same
        radix-selected butterflies as the single-device kernels."""
        v = FJ.to_mont(FR, v)
        if pre is not None:
            v = FJ.mont_mul(FR, v, pre)
        v = ntt_jax.run_stages(v, core)
        if mid is not None:
            v = FJ.mont_mul(FR, v, mid)
        if post is not None:
            v = FJ.mont_mul(FR, v, post)
        return FJ.from_mont(FR, v)

    def _plan_consts(self, size, inverse):
        # keyed on the active radix: the fleet panels follow the same
        # DPT_NTT_RADIX knob as the single-device and mesh paths
        key = ("plan", size, inverse, ntt_jax._active_radix())
        if key not in self._tables:
            plan = ntt_jax.get_plan(size)
            self._tables[key] = {
                k: jnp.asarray(a)
                for k, a in plan.core_consts(inverse).items()}
        return self._tables[key]

    def _cache_put(self, key, value):
        """Tables are stored as DEVICE arrays: numpy here would re-pay a
        host->device transfer of up to tens of MB per FFT frame."""
        if len(self._tables) >= self._TABLE_CAP:
            self._tables.pop(next(iter(self._tables)))
        value = jax.tree_util.tree_map(jnp.asarray, value)
        self._tables[key] = value
        return value

    def _stage1_tables(self, task, rs, re):
        """(pre, mid) Montgomery tables for global rows j2 in [rs, re)."""
        key = ("s1", task.n, task.inverse, task.coset, rs, re)
        if key in self._tables:
            return self._tables[key]
        n, r, c = task.n, task.r, task.c
        pre = None
        if task.coset and not task.inverse:
            vals = []
            gc = pow(FR_GENERATOR, c, R_MOD)
            for j2 in range(rs, re):
                vals.extend(ntt_jax._powers(
                    gc, r, start=pow(FR_GENERATOR, j2, R_MOD)))
            pre = ntt_jax._mont_table(vals).reshape(16, re - rs, r)
        w = fr_root_of_unity(n)
        base = fr_inv(w) if task.inverse else w
        # the stage core (run_stages) omits the 1/size factor of an iNTT: fold the
        # stage-1 1/r into the mid twiddles (the int path's backend.ifft
        # applies it internally)
        start0 = fr_inv(r % R_MOD) if task.inverse else 1
        vals = []
        for j2 in range(rs, re):
            vals.extend(ntt_jax._powers(pow(base, j2, R_MOD), r, start=start0))
        mid = ntt_jax._mont_table(vals).reshape(16, re - rs, r)
        return self._cache_put(key, (pre, mid))

    def _stage2_tables(self, task, cs, ce):
        """post Montgomery table for global columns k1 in [cs, ce):
        inverse-coset scales g^-(k1 + r*k2) plus the stage-2 1/c factor
        (the 1/n of a full iNTT = the 1/r folded into stage 1's mids times
        this 1/c, as in the reference's two stage iFFTs)."""
        key = ("s2", task.n, task.inverse, task.coset, cs, ce)
        if key in self._tables:
            return self._tables[key]
        post = None
        if task.inverse:
            c_inv = fr_inv(task.c % R_MOD)
            if task.coset:
                g_inv = fr_inv(FR_GENERATOR)
                step = pow(g_inv, task.r, R_MOD)
                vals = []
                for k1 in range(cs, ce):
                    vals.extend(ntt_jax._powers(
                        step, task.c,
                        start=c_inv * pow(g_inv, k1, R_MOD) % R_MOD))
                post = ntt_jax._mont_table(vals).reshape(16, ce - cs, task.c)
            else:
                post = ntt_jax._mont_table([c_inv]).reshape(16, 1, 1)
        return self._cache_put(key, post)

    def stage1_panel(self, task, first_row, panel):
        """(16, B, r) canonical limb panel for rows [first_row, ...) ->
        staged panel (numpy)."""
        b = panel.shape[1]
        pre, mid = self._stage1_tables(task, first_row, first_row + b)
        core = self._plan_consts(task.r, task.inverse)
        out = self._panel_fn(panel, pre, mid, None, core)
        return np.asarray(out)

    def stage2_panel(self, task, cols_panel):
        """(16, locals, c) canonical columns panel -> staged output panel
        (numpy), ready for the wire."""
        post = self._stage2_tables(task, task.cs, task.ce)
        core = self._plan_consts(task.c, task.inverse)
        out = self._panel_fn(cols_panel, None, None, post, core)
        return np.asarray(out)
