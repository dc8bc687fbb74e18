"""Block-sparse kernel microbenchmark: Pallas block-skipping attention
(ops/block_sparse.py) vs the XLA dense+mask path, matched shapes/pattern.

SURVEY §7.7 keep-or-kill rule: a kernel must beat the XLA baseline on
hardware to be kept. This prints one JSON line per config:

  {"n": N, "block": B, "live_frac": f, "dense_ms": X, "sparse_ms": Y,
   "speedup": X/Y, "platform": ...}

Run on the TPU (`python tools/bench_blocksparse.py` from the checkout,
through the chip tool). On CPU the Mosaic path cannot lower —
the script emits a labeled skip line instead of timing interpret mode
(which benchmarks nothing real).

Two pattern sources:

- default: the static banded+global pattern at each bucket edge
  (window=1, num_global=1 — the serving KernelPolicy's first-pass
  mask). Block sparsity pays off at long N: at N=1024 the live
  fraction is ~0.53, at N=2048 ~0.29.
- `--from-contacts FILE.npz` (ISSUE 12): replay SAVED pair activations
  — a `distogram` (b, n, n, buckets) logits array (save one from
  `predict.fold_init(...).distogram`) or a precomputed `contacts`
  (n, n) probability map — through the same
  `ops.block_sparse.contact_block_pattern` planner the serving
  scheduler uses, and bench the MEASURED live fraction per bucket
  edge. `--append tools/tpu_blocksparse.json` appends the results
  (tagged "source": "contacts") so the auto kernel policy's
  sparse-live-frac threshold is backed by live fractions real targets
  produce instead of guessed from the banded geometry.
  `--emit-synthetic FILE.npz` writes a plausible synthetic
  pair-activation file (banded backbone + off-diagonal domain
  contacts) for trying the flow without a TPU fold.

Shapes mirror the Evoformer axial-attention layout after head folding
(B = batch*heads, N = crop length, D = head dim).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--buckets", default="512,1024,2048",
                    help="comma-separated bucket edges (N) to bench")
    ap.add_argument("--block", type=int,
                    default=int(os.environ.get("BSB_BLOCK", 128)))
    ap.add_argument("--batch", type=int,
                    default=int(os.environ.get("BSB_BATCH", 8)))
    ap.add_argument("--iters", type=int,
                    default=int(os.environ.get("BSB_ITERS", 20)))
    ap.add_argument("--from-contacts", default="",
                    help="npz with 'distogram' (b,n,n,buckets) logits "
                         "or 'contacts' (n,n) probabilities: plan the "
                         "per-bucket pattern from it instead of the "
                         "static banded mask")
    ap.add_argument("--contact-cutoff", type=float, default=8.0,
                    help="contact distance (A) for P(d < cutoff)")
    ap.add_argument("--contact-threshold", type=float, default=0.5,
                    help="block live when max cell P(contact) >= this")
    ap.add_argument("--append", default="",
                    help="append result lines to this JSON array file "
                         "(e.g. tools/tpu_blocksparse.json)")
    ap.add_argument("--emit-synthetic", default="",
                    help="write a synthetic pair-activation npz here "
                         "and exit (demo/test input for "
                         "--from-contacts)")
    ap.add_argument("--emit-n", type=int, default=2048,
                    help="sequence length of --emit-synthetic")
    return ap.parse_args(argv)


def _synthetic_contacts(n: int, seed: int = 0):
    """A plausible (n, n) contact-probability map: strong short-range
    band (backbone neighbors), a few off-diagonal domain-contact
    patches, weak background."""
    import numpy as np

    rng = np.random.default_rng(seed)
    i = np.arange(n)
    d = np.abs(i[:, None] - i[None, :])
    probs = np.exp(-d / 12.0)                      # banded backbone
    for _ in range(max(3, n // 256)):              # domain contacts
        a, b = sorted(rng.integers(0, n, 2))
        w = int(rng.integers(16, 64))
        probs[a:a + w, b:b + w] = np.maximum(
            probs[a:a + w, b:b + w], rng.uniform(0.6, 0.95))
    probs = np.maximum(probs, probs.T)
    return np.clip(probs + rng.uniform(0, 0.05, (n, n)), 0.0, 1.0)


def _load_contacts(args):
    """(n, n) contact probabilities from the --from-contacts npz."""
    import numpy as np

    from alphafold2_tpu.ops.block_sparse import \
        contact_probs_from_distogram

    with np.load(args.from_contacts) as z:
        if "contacts" in z:
            return np.asarray(z["contacts"], np.float32)
        if "distogram" in z:
            return contact_probs_from_distogram(
                z["distogram"], cutoff=args.contact_cutoff)
    raise SystemExit(f"{args.from_contacts}: neither 'contacts' nor "
                     "'distogram' array found")


def _fit_contacts(contacts, n: int):
    """Crop (or wrap-tile) the saved map to bucket edge n — the replay
    benches every configured edge from one saved target."""
    import numpy as np

    m = contacts.shape[0]
    if m >= n:
        return contacts[:n, :n]
    reps = -(-n // m)
    return np.tile(contacts, (reps, reps))[:n, :n]


def _pattern_for(args, n: int, contacts):
    from alphafold2_tpu.model.attention_variants import \
        block_sparse_block_pattern
    from alphafold2_tpu.ops.block_sparse import contact_block_pattern

    if contacts is None:
        return block_sparse_block_pattern(n // args.block, num_global=1,
                                          window=1), "static"
    return contact_block_pattern(
        _fit_contacts(contacts, n), args.block,
        threshold=args.contact_threshold), "contacts"


def _append_json(path: str, lines):
    """Append result dicts to a JSON array file (created if absent)."""
    existing = []
    if os.path.exists(path):
        with open(path) as fh:
            existing = json.load(fh)
    existing.extend(lines)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(existing, fh, indent=1)
        fh.write("\n")
    os.replace(tmp, path)


def main(argv=None):
    args = parse_args(argv)
    if args.emit_synthetic:
        import numpy as np
        np.savez_compressed(args.emit_synthetic,
                            contacts=_synthetic_contacts(args.emit_n))
        print(json.dumps({"emitted": args.emit_synthetic,
                          "n": args.emit_n}), flush=True)
        return

    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.runtime import enable_compile_cache, on_tpu
    enable_compile_cache()

    platform = jax.default_backend()
    contacts = _load_contacts(args) if args.from_contacts else None
    buckets = [int(x) for x in args.buckets.split(",") if x]

    if not on_tpu():
        # no timing off-TPU (interpret mode benchmarks nothing real),
        # but the --from-contacts replay still reports the MEASURED
        # live fraction per bucket edge — the number the auto policy's
        # threshold is calibrated against
        lines = []
        for n in buckets:
            pattern, source = _pattern_for(args, n, contacts)
            lines.append({
                "skipped": True, "platform": platform, "n": n,
                "block": args.block, "source": source,
                "live_frac": round(float(pattern.mean()), 3),
                "reason": "Mosaic lowering needs a TPU; interpret-mode "
                          "timing is not evidence (exactness is "
                          "covered by tests/test_ops.py)"})
            print(json.dumps(lines[-1]), flush=True)
        if args.append and contacts is not None:
            _append_json(args.append, lines)
        return

    from alphafold2_tpu.ops.attention import MASK_VALUE
    from alphafold2_tpu.ops.block_sparse import block_sparse_attention

    B, D = args.batch, 64
    block, iters = args.block, args.iters

    lines = []
    for n in buckets:
        pattern, source = _pattern_for(args, n, contacts)
        live_frac = float(pattern.mean())
        rng = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (B, n, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, n, D), jnp.bfloat16)
        v = jax.random.normal(kv, (B, n, D), jnp.bfloat16)

        import numpy as np
        tok = np.repeat(np.repeat(pattern, block, 0), block, 1)
        bias = jnp.where(jnp.asarray(tok), 0.0, MASK_VALUE)[None]
        bias = jnp.broadcast_to(bias, (B, n, n)).astype(jnp.float32)

        @jax.jit
        def dense(q, k, v, bias):
            logits = jnp.einsum("bnd,bmd->bnm", q.astype(jnp.float32),
                                k.astype(jnp.float32)) * (D ** -0.5)
            attn = jax.nn.softmax(logits + bias, axis=-1)
            return jnp.einsum("bnm,bmd->bnd", attn,
                              v.astype(jnp.float32)).astype(q.dtype)

        # pattern is STATIC (host-side plan); close it into the jitted fn
        # rather than passing it as a (traced) argument
        sparse = jax.jit(functools.partial(
            block_sparse_attention, pattern=pattern, block=block))

        def timeit(fn, *args_):
            # Measurement discipline:
            # (a) close the window with a device_get of a scalar
            #     reduction (a transfer cannot complete before the
            #     compute it depends on);
            # (b) per-call dispatch can swamp ms-scale kernels — run the
            #     whole window as ONE dispatch: a lax.scan of `iters`
            #     chained applications (output feeds back as q,
            #     serializing on-device).
            @jax.jit
            def window(q0, rest):
                def body(q, _):
                    return fn(q, *rest), None
                out, _ = jax.lax.scan(body, q0, None, length=iters)
                return jnp.sum(out.astype(jnp.float32))

            float(jax.device_get(window(args_[0], args_[1:])))  # warm
            t0 = time.perf_counter()
            s = window(args_[0], args_[1:])
            float(jax.device_get(s))
            return (time.perf_counter() - t0) / iters * 1e3

        dense_ms = timeit(dense, q, k, v, bias)
        sparse_ms = timeit(sparse, q, k, v)
        lines.append({
            "n": n, "block": block, "batch": B, "dim_head": D,
            "source": source,
            "live_frac": round(live_frac, 3),
            "dense_ms": round(dense_ms, 3),
            "sparse_ms": round(sparse_ms, 3),
            "speedup": round(dense_ms / sparse_ms, 3),
            "platform": platform})
        print(json.dumps(lines[-1]), flush=True)
    if args.append:
        _append_json(args.append, lines)


if __name__ == "__main__":
    main()
