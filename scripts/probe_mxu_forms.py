"""Probe which matmul formulation Mosaic compiles fast inside a Pallas
kernel on real hardware — the decision input for the MXU-REDC path.

The first predc attempt (int8 einsum "kl,...lb->...kb" inside the Miller
kernel) timed out after 1500 s of compilation. This script times each
candidate form in its own subprocess with a hard deadline (the parent
never imports JAX, so each child in turn holds the chip):

  i8_einsum   int8 einsum, batch dims folded into ...
  i8_batched  int8 lax.dot_general with explicit batch dims
  bf16_einsum bf16 operands, f32 accumulation (exact: 7-bit digits,
              column sums <= 2^19 << 2^24)
  bf16_batched

Run on the chip:  python scripts/probe_mxu_forms.py
Appends results to MXU_FORM_PROBES.jsonl.
"""

import datetime
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORMS = ["bf16_batched", "bf16_einsum", "i8_batched", "i8_einsum"]
DEADLINE = 420

# The child deliberately enables NO persistent compile cache: each probe
# measures a cold Mosaic compile, which is the quantity under test.
INNER = r"""
import time
import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

form = %(form)r
S, L, K, B = 18, 32, 64, 128
rng = np.random.default_rng(0)
M = rng.integers(0, 127, (K, L), dtype=np.int32)
X = rng.integers(0, 127, (S, L, B), dtype=np.int32)


def contract(m, x):
    if form.startswith("bf16"):
        m = m.astype(jnp.bfloat16)
        x = x.astype(jnp.bfloat16)
        acc = jnp.float32
    else:
        m = m.astype(jnp.int8)
        x = x.astype(jnp.int8)
        acc = jnp.int32
    if form.endswith("einsum"):
        out = jnp.einsum("kl,slb->skb", m, x, preferred_element_type=acc)
    else:
        mb = jnp.broadcast_to(m[None], (S,) + m.shape)
        out = jax.lax.dot_general(
            mb, x,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=acc,
        )
    return out.astype(jnp.int32)


def kernel(m_ref, x_ref, o_ref):
    o_ref[:] = contract(m_ref[:], x_ref[:])


@jax.jit
def run(m, x):
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, K, B), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
    )(m, x)


t0 = time.perf_counter()
out = np.asarray(run(jnp.asarray(M), jnp.asarray(X)))
compile_s = time.perf_counter() - t0
t0 = time.perf_counter()
jax.block_until_ready(run(jnp.asarray(M), jnp.asarray(X)))
run_s = time.perf_counter() - t0
ref = np.einsum("kl,slb->skb", M.astype(np.int64), X.astype(np.int64))
print("RESULT", form, np.array_equal(out, ref.astype(np.int32)),
      round(compile_s, 1), round(run_s * 1e3, 2))
"""


def main():
    results = []
    for form in FORMS:
        code = INNER % {"form": form}
        try:
            r = subprocess.run(
                [sys.executable, "-c", code],
                timeout=DEADLINE,
                capture_output=True,
            )
            lines = [
                ln
                for ln in r.stdout.decode(errors="replace").splitlines()
                if ln.startswith("RESULT")
            ]
            if lines:
                _, f, ok, comp, ms = lines[-1].split()
                rec = {
                    "form": f,
                    "exact": ok == "True",
                    "compile_s": float(comp),
                    "run_ms": float(ms),
                }
            else:
                tail = r.stderr.decode(errors="replace").splitlines()[-3:]
                rec = {"form": form, "error": " | ".join(tail)[-400:]}
        except subprocess.TimeoutExpired:
            rec = {"form": form, "error": f"compile TIMEOUT {DEADLINE}s"}
        rec["recorded_at"] = datetime.datetime.now(
            datetime.timezone.utc
        ).isoformat(timespec="seconds")
        print(json.dumps(rec))
        results.append(rec)
        with open(os.path.join(REPO, "MXU_FORM_PROBES.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
