"""Stage-wise hardware profile of the Pallas batch-verify pipeline.

Times each stage of verify_signature_sets_pallas separately on the real
chip (own jit per stage, block_until_ready between reps) to locate the
per-signature cost: the RLC ladder kernels + XLA glue (stage A), the
fused Miller kernel (stage B), and the XLA fold + final exponentiation
tail (stage C). Writes one JSON line per stage to stdout and appends a
combined record to PROFILE_PALLAS.jsonl.

Run on the chip:
    python scripts/profile_pallas.py [S]
"""

import datetime
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lighthouse_tpu.backend import enable_compile_cache  # noqa: E402

enable_compile_cache()


def main():
    n_sets = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
    reps = 5

    import functools

    import numpy as np
    import jax

    from lighthouse_tpu import testing as td
    from lighthouse_tpu.ops import batch_verify, tfield as tf, tower, pairing

    platform = jax.default_backend()
    args = jax.device_put(
        td.make_signature_set_batch(
            n_sets, max_keys=1, seed=0, fast_sequential=True
        )
    )

    inputs_fn = jax.jit(
        functools.partial(batch_verify.miller_inputs_pallas, block_b=128)
    )

    def miller_only(*a):
        from lighthouse_tpu.ops.pallas_miller import miller_loop_pallas

        g1s, g2s, pm = batch_verify.miller_inputs_pallas(*a, block_b=128)
        n_pairs = g1s[0].shape[0]
        pad = (-n_pairs) % 128

        def pad0(c):
            widths = [(0, pad)] + [(0, 0)] * (c.ndim - 1)
            return jax.numpy.pad(c, widths)

        g1s = tuple(pad0(c) for c in g1s)
        g2s = tuple(pad0(c) for c in g2s)
        pm = jax.numpy.pad(pm, (0, pad))
        p_t = tuple(tf.from_batchlead(c) for c in g1s)
        q_t = tuple(tf.from_batchlead(c) for c in g2s)
        return miller_loop_pallas(p_t, q_t, pm, block_b=128)

    miller_fn = jax.jit(miller_only)

    def tail_only(f_t):
        f = tf.to_batchlead(f_t)
        prod = tower.fp12_product_axis(f, axis=0)
        return pairing.final_exp_is_one(prod)

    tail_fn = jax.jit(tail_only)

    full_fn = jax.jit(
        functools.partial(
            batch_verify.verify_signature_sets_pallas, block_b=128
        )
    )

    def timeit(fn, *a):
        out = fn(*a)
        jax.block_until_ready(out)  # compile+warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            ts.append(time.perf_counter() - t0)
        return out, sorted(ts)[len(ts) // 2]

    inputs_out, t_inputs = timeit(inputs_fn, *args)
    f_t, t_miller_plus_inputs = timeit(miller_fn, *args)
    _, t_tail = timeit(tail_fn, f_t)
    ok, t_full = timeit(full_fn, *args)
    assert bool(np.asarray(ok)), "profile batch failed to verify"

    rec = {
        "n_sets": n_sets,
        "platform": platform,
        "p50_inputs_s": round(t_inputs, 4),
        "p50_miller_kernel_s": round(t_miller_plus_inputs - t_inputs, 4),
        "p50_tail_s": round(t_tail, 4),
        "p50_full_s": round(t_full, 4),
        "recorded_at": datetime.datetime.now(
            datetime.timezone.utc
        ).isoformat(timespec="seconds"),
    }
    print(json.dumps(rec))
    with open(os.path.join(REPO, "PROFILE_PALLAS.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
