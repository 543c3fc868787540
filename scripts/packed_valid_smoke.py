#!/usr/bin/env python
"""Chip smoke for the packed flash kernels' dynamic-valid SMEM path.

The ring composition is the only caller of ``valid=`` (a device scalar in
SMEM) + ``masked_sentinel=-inf`` — and ring needs a seq-axis >= 2, which
a single chip cannot provide. This drives that exact kernel
configuration directly on one chip (no mesh): packed fwd/bwd with a
rotating device-scalar validity count, checked against the folded kernels
and a masked dense reference. Writes perf/packed_valid_smoke.json.

The 4D grid + SMEM scalar + leading-dim-2 lse blocks are the Mosaic-only
risk interpret mode cannot vouch for (PERF_ANALYSIS.md §10f, r3 lesson).
"""

from __future__ import annotations

import importlib
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("tpuic.kernels.flash_attention")
    b, n, h, d = 2, 64, 4, 64
    assert fa._use_packed(h, d)
    key = jax.random.key(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (b, n, h, d),
                                 jnp.float32) for i in range(3))
    bq, bk = fa._resolve_blocks(n, None, None)
    interp = jax.devices()[0].platform != "tpu"
    rows = []
    for vl in (n, 40, 0):  # full, partial, FULLY-masked (sentinel path)
        valid = jnp.asarray([vl], jnp.int32)
        o_p, lse_p = fa._flash_fwd_packed(
            q, k, v, bq, bk, interp, with_lse=True, valid=valid,
            masked_sentinel=fa._NEG_INF)
        o_f, lse_f = fa._flash_fwd(
            q, k, v, bq, bk, interp, with_lse=True, valid=valid,
            masked_sentinel=fa._NEG_INF)
        g = jnp.ones_like(q)
        g_p = fa._flash_bwd_packed(q, k, v, o_p, lse_p, g, bq, bk, interp,
                                   valid=valid)
        g_f = fa._flash_bwd(q, k, v, o_f, lse_f, g, bq, bk, interp,
                            valid=valid)
        diffs = {
            "o": float(jnp.abs(o_p - o_f).max()),
            "lse": float(jnp.abs(lse_p - lse_f).max()),
            **{name: float(jnp.abs(a - c).max())
               for name, a, c in zip(("dq", "dk", "dv"), g_p, g_f)},
        }
        if vl > 0:  # dense cross-check on the valid slice
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k[:, :vl]) / np.sqrt(d)
            ref = jnp.einsum("bhqk,bkhd->bqhd",
                             jax.nn.softmax(s, -1), v[:, :vl])
            diffs["o_vs_dense"] = float(jnp.abs(o_p - ref).max())
        ok = all(x < 1e-4 for x in diffs.values())
        rows.append({"valid": vl, "ok": ok, "max_diffs": diffs})
        print(json.dumps(rows[-1]), flush=True)

    out = {"device": str(jax.devices()[0].device_kind),
           "platform": jax.devices()[0].platform,
           "blocks": [bq, bk], "shape": [b, n, h, d],
           "ok": all(r["ok"] for r in rows), "rows": rows}
    path = os.path.join(_REPO, "perf", "packed_valid_smoke.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {path}; ok={out['ok']}")
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
