"""TPU benchmark for speculative decoding (runtime/speculative.py).

Random-init models cannot show a realistic draft acceptance rate, so the
measurement brackets the deployment envelope instead:

- ``decode_baseline``: plain PipelinedDecoder tokens/s, same geometry —
  the number speculative decoding must beat;
- ``spec_floor_*``: a cheap 2-layer draft with random weights (near-zero
  acceptance) — worst case, every round wastes its proposals;
- ``spec_perfect_*``: draft == target (acceptance 1.0) — the
  verification machinery at its ceiling, target forwards ~ new/(gamma+1)
  (the draft recompute here costs a full target forward per proposed
  token, so tokens/s is NOT the headline — ``target_forwards`` is);
- ``primitives``: measured seconds per verification forward (the
  length-bucketed ``Defer.logits``) and per draft forward, from which
  projected tokens/s at any acceptance rate follows analytically:
  E[tokens/round] = (1 - a^(g+1)) / (1 - a), round cost =
  g * t_draft + t_target.

If ``DEFER_SPEC_OUT`` is set, the artifact is rewritten after every
row (atomic, merging — ``defer_tpu.utils.artifact``), so a timeout
keeps completed rows; the final JSON line always prints on stdout.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax

    from defer_tpu import Defer, DeferConfig, speculative_generate
    from defer_tpu.models import gpt
    from defer_tpu.runtime.decode import PipelinedDecoder

    devices = jax.devices()
    on_tpu = devices[0].platform != "cpu"
    if on_tpu:
        tl, td, th = 12, 768, 12          # GPT-2-small target
        dl, dd, dh = 2, 256, 4            # cheap draft (~12% of target)
        vocab, max_len, plen, new, mb = 50257, 256, 32, 128, 8
        # every proposed-but-rejected token costs a full-sequence
        # forward; DEFER_SPEC_NEW trims the per-row round count for a
        # bounded re-run window
        new = int(os.environ.get("DEFER_SPEC_NEW", new))
        if plen + new > max_len:
            raise SystemExit(
                f"DEFER_SPEC_NEW={new}: prompt {plen} + new {new} exceeds "
                f"the decode buffer max_len {max_len}")
        gammas = tuple(int(g) for g in os.environ.get(
            "DEFER_SPEC_GAMMAS", "1,3,5").split(","))
        cd = "bfloat16"
    else:  # CPU smoke
        tl, td, th = 4, 64, 2
        dl, dd, dh = 2, 32, 2
        vocab, max_len, plen, new, mb = 128, 64, 8, 16, 2
        cd = None

    out = {
        "metric": "gpt_small_speculative_decode",
        "platform": devices[0].platform,
        "config": {"target_layers": tl, "d_target": td, "draft_layers": dl,
                   "d_draft": dd, "vocab": vocab, "prompt_len": plen,
                   "new_tokens": new, "batch": mb, "max_len": max_len},
    }
    out["value"] = 0.0
    out["unit"] = "tokens/sec"
    rows = {}
    out_path = os.environ.get("DEFER_SPEC_OUT")

    from defer_tpu.utils.artifact import flush_artifact

    def flush():
        # headline = best REALISTIC speculative row (spec_floor_*);
        # decode_baseline is the comparator and spec_perfect_* is a
        # machinery diagnostic (oracle draft), neither is the result
        out["rows"] = rows
        return flush_artifact(
            out_path, dict(out), merge_key="rows",
            row_filter=lambda k: k.startswith("spec_floor"))

    target = gpt(tl, td, th, max_len, vocab=vocab, name="spec_target")
    tparams = target.init(jax.random.key(0))
    draft = gpt(dl, dd, dh, max_len, vocab=vocab, name="spec_draft")
    dparams = draft.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, vocab, (mb, plen)).astype(np.int64)

    import jax.numpy as jnp
    cfg = DeferConfig(microbatch=mb, chunk=8,
                      compute_dtype=getattr(jnp, cd) if cd else None)
    defer = Defer(config=cfg)

    # -- plain decode baseline --------------------------------------------
    dec = PipelinedDecoder(target, tparams, num_stages=1, microbatch=mb,
                           max_len=max_len,
                           compute_dtype=getattr(jnp, cd) if cd else None)
    kw = dict(max_new_tokens=new, token_chunk=32)
    dec.generate(prompt.astype(np.int32), **kw)          # compile
    t0 = time.perf_counter()
    dec.generate(prompt.astype(np.int32), **kw)
    dt = time.perf_counter() - t0
    rows["decode_baseline"] = {"tokens_per_s": round(mb * new / dt, 2),
                               "wall_s": round(dt, 3)}
    print(f"decode_baseline: {mb * new / dt:.1f} tok/s", file=sys.stderr,
          flush=True)
    del dec
    flush()

    # -- speculative rows --------------------------------------------------
    def spec_row(tag, dg, dp, gamma, warm=True):
        a = dict(gamma=gamma, num_stages=1, draft_num_stages=1,
                 return_stats=True)
        if warm:  # buckets compile on first call
            speculative_generate(defer, target, tparams, dg, dp,
                                 prompt, new, **a)
        t0 = time.perf_counter()
        _, stats = speculative_generate(defer, target, tparams, dg, dp,
                                        prompt, new, **a)
        dt = time.perf_counter() - t0
        rows[tag] = {"tokens_per_s": round(mb * new / dt, 2),
                     "wall_s": round(dt, 3),
                     "accept_rate": round(stats["accept_rate"], 4),
                     "rounds": stats["rounds"],
                     "target_forwards": stats["target_forwards"],
                     "draft_forwards": stats["draft_forwards"]}
        print(f"{tag}: {mb * new / dt:.1f} tok/s "
              f"accept={stats['accept_rate']:.3f} "
              f"tf={stats['target_forwards']}", file=sys.stderr, flush=True)
        flush()

    for gamma in gammas if on_tpu else (3,):
        spec_row(f"spec_floor_g{gamma}", draft, dparams, gamma)
    spec_row("spec_perfect_g3", target, tparams, 3)

    # -- primitives: per-forward costs at the top bucket -------------------
    full = rng.integers(0, vocab, (mb, plen + new)).astype(np.int64)
    for name, g, p in (("t_target_fwd_s", target, tparams),
                       ("t_draft_fwd_s", draft, dparams)):
        defer.logits(g, p, full, num_stages=1)           # compile
        t0 = time.perf_counter()
        defer.logits(g, p, full, num_stages=1)
        rows.setdefault("primitives", {})[name] = round(
            time.perf_counter() - t0, 4)
    # projected tokens/s vs draft acceptance from the measured primitives
    tt = rows["primitives"]["t_target_fwd_s"]
    tdr = rows["primitives"]["t_draft_fwd_s"]
    proj = {}
    for a in (0.5, 0.7, 0.8, 0.9):
        g = 3
        exp_tokens = (1 - a ** (g + 1)) / (1 - a)
        proj[f"a{a}"] = round(mb * exp_tokens / (g * tdr + tt), 1)
    rows["projected_tokens_per_s_g3"] = proj
    print(json.dumps(flush()))


if __name__ == "__main__":
    main()
