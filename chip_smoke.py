"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # on a TPU host, from a checkout

One process drives the main path once, through the entry points a user
calls, at the full width of models the zoo already has (depth uncut,
weights random from a seed), on however many chips the host has
(``n = len(jax.devices())``, 1 or 4):

* **ring** — ``resnet50()`` at 224x224x3, bf16 compute, ``num_stages=n``,
  streamed through ``Defer.build(...).run`` (``SpmdPipeline`` on
  ``pipeline_mesh(n)``), once with ``wire="buffer"`` and once with
  ``wire="int8"``; outputs against the single-program
  ``jax.jit(graph.apply)`` forward, and the n-stage result against the
  1-stage one;
* **serve/tensor** — the ``serve`` command's deployment
  (``cli.serve_deployment``): front door -> ``ChainBackend`` ->
  ``ChainDispatcher.deploy`` -> four ``StageNode`` threads running
  ResNet50 f32 stage artifacts, stage k pinned to device ``k % n``; two
  tenants' requests through ``ServeClient``, every answer against the
  solo forward, no sheds;
* **decode** — ``gpt_small()`` (12 layers, d 768, vocab 50257) through
  ``PipelinedDecoder(num_stages=n)``: ``generate(prefill=True)`` (the
  fused prefill is what compiles the flash kernel), the decode-rate
  path, and a single-program greedy reference must agree token for
  token;
* **serve/decode** — ``serve --workload decode``
  (``ContinuousBatchEngine``): requests join and leave mid-stream, each
  output equal to the request run alone.  This engine computes on ONE
  chip whatever ``--stages`` says;
* **export** — a transformer stage -> ``export_stage_bytes`` ->
  ``load_stage_program`` -> run: the flash kernel rides inside the
  exported artifact.

For every phase it prints the compile seconds (XLA backend compiles,
from ``jax.monitoring``) and the run seconds separately, the ids of the
devices that actually held the weights and ran the programs (read from
shardings and node ``stats``, not from the request), and — before and
after the first large executable — the host round trip of a trivial
jitted op.  That round trip is an observation, not a metric.

It fails — non-zero exit, no result line — when jax finds no TPU, when
``defer_tpu.utils.hw`` does not know the ``device_kind``, when the
device count is not 1 or 4, and when any phase fails: no ``except`` on
this path turns a failure into a log line.  It sets no
``JAX_PLATFORMS`` and starts no process.  The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": n}}

Run it twice in one call (cold, then warm) to see the compile cache
(``defer_tpu/utils/compile_cache.py``) work: the second pass reports
less compile time.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import time

import numpy as np

#: Stated tolerances, each relative to max|reference| over the compared
#: block (the normalisation ``bench.py``'s ``rel_logit_err`` uses).  The
#: first v5e runs measured 0.0068 / 0.0047 / 0.0070 / 0.0028 for the
#: first four; the bounds leave ~3x for other tilings and chip counts.
TOL = {
    # bf16 pipeline vs the bf16 single program: same arithmetic, other
    # fusion boundaries — a few bf16 ulps (2^-8) through 50 layers
    "ring_vs_bf16_program": 2e-2,
    # bf16 pipeline vs the f32 single program: bf16 rounding end to end
    "ring_vs_f32_program": 2e-2,
    # int8 wire vs f32: adds <= 1/254 of each block's max per hop
    "int8_rel_logit_err": 3e-2,
    # f32 stage artifacts at batch W vs the f32 solo forward at batch 1
    # (TPU f32 convs multiply in bf16 passes; tilings differ by batch)
    "serve_vs_solo": 1e-2,
    # n-stage vs 1-stage ring (both bf16)
    "n_stage_vs_1_stage": 2e-2,
    # exported stage program vs the same stage function jitted directly
    "export_round_trip": 1e-3,
}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run drives.  ``FULL`` is the chip run; the tier-1 test
    imports this module and runs ``TINY`` on the CPU host mesh so the
    script cannot rot between chip runs."""

    cnn: str                    # defer_tpu.models factory names
    lm: str
    expect_mosaic: bool         # lowered programs must hold TPU kernels
    microbatch: int = 8
    chunk: int = 8
    chunks: int = 3
    serve_stages: int = 4
    serve_width: int = 8
    serve_requests: int = 6     # per tenant
    lm_microbatch: int = 2
    prompt_len: int = 16
    new_tokens: int = 16
    lm_max_len: int = 64
    engine_width: int = 4


FULL = Sizes(cnn="resnet50", lm="gpt_small", expect_mosaic=True)
TINY = Sizes(cnn="resnet_tiny", lm="gpt_tiny", expect_mosaic=False,
             microbatch=2, chunk=2, chunks=2, serve_stages=3,
             serve_width=2, serve_requests=3, lm_microbatch=1,
             prompt_len=4, new_tokens=5, lm_max_len=16, engine_width=2)


def say(*a):
    print(*a, flush=True)


def rel_err(got, ref) -> float:
    ref = np.asarray(ref, np.float32)
    got = np.asarray(got, np.float32)
    return float(np.abs(got - ref).max() / max(float(np.abs(ref).max()),
                                               1e-6))


def shard_device_ids(arr) -> list[int]:
    """Device id of every addressable shard, in shard order."""
    return [s.device.id for s in arr.addressable_shards]


class Run:
    """One smoke run: the devices, the compile listener, the report."""

    def __init__(self, sizes: Sizes, devices):
        import jax

        from defer_tpu.obs import REGISTRY
        from defer_tpu.obs.profile import recompile_watcher
        self.sizes = sizes
        self.devices = list(devices)
        self.n = len(self.devices)
        recompile_watcher().install()
        self._compiles = REGISTRY.counter("jax.compiles")
        self._compile_s = REGISTRY.histogram("jax.compile_s")
        self._cache_hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        self.report: dict = {"phases": {}, "sync_rtt_ms": {}}
        self._models: dict = {}

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self._cache_hits += 1

    def sync_rtt(self, label: str, iters: int = 50) -> None:
        """Host round trip of a trivial jitted op (dispatch + sync)."""
        import jax
        import jax.numpy as jnp
        f = jax.jit(lambda x: x + 1)
        x = jnp.zeros((8,), jnp.float32)
        f(x).block_until_ready()
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            f(x).block_until_ready()
            ts.append((time.perf_counter() - t0) * 1e3)
        ts.sort()
        row = {"min": round(ts[0], 4), "p50": round(ts[iters // 2], 4),
               "p90": round(ts[int(iters * 0.9)], 4)}
        self.report["sync_rtt_ms"][label] = row
        say(f"observation sync_rtt[{label}] ms: {row} "
            f"(trivial jitted op, dispatch + block_until_ready)")

    def phase(self, name: str) -> "Phase":
        return Phase(self, name)

    def model(self, name: str, seed: int):
        """``(graph, params)`` of a zoo model, random weights from
        ``seed`` — built once per run, not once per phase."""
        import jax

        from defer_tpu import models
        key = (name, seed)
        if key not in self._models:
            graph = getattr(models, name)()
            self._models[key] = (graph, graph.init(jax.random.key(seed)))
        return self._models[key]


class Phase:
    """Times one phase; a failure inside propagates (and fails the run).

    ``compile_s`` sums the XLA backend compiles jax reported while the
    phase ran (a persistent-cache hit reports its retrieval time);
    ``first_call_s`` / ``run_s`` are host wall clocks the phase body
    fills in around work that ends in a device sync."""

    def __init__(self, run: Run, name: str):
        self.run, self.name = run, name
        self.row: dict = {}

    def __enter__(self) -> "Phase":
        r = self.run
        self._c0, self._s0 = r._compiles.value, r._compile_s.sum
        self._h0 = r._cache_hits
        self._t0 = time.perf_counter()
        say(f"phase {self.name}: start")
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            say(f"phase {self.name}: FAILED {exc_type.__name__}: {exc}")
            return False
        r = self.run
        self.row = {
            "compiles": int(r._compiles.value - self._c0),
            "compile_s": round(r._compile_s.sum - self._s0, 3),
            "cache_hits": r._cache_hits - self._h0,
            "wall_s": round(time.perf_counter() - self._t0, 3),
            **self.row,
        }
        r.report["phases"][self.name] = self.row
        say(f"phase {self.name}: passed {json.dumps(self.row)}")
        return False

    def note(self, **kw) -> None:
        self.row.update(kw)

    def check(self, what: str, value: float, bound: float) -> None:
        self.row[what] = round(value, 6)
        if not value <= bound:
            raise AssertionError(
                f"{self.name}: {what} = {value:.6g} exceeds the stated "
                f"tolerance {bound:g}")


def lowered_kernel_count(jitted, *args) -> int:
    """TPU (Mosaic) custom calls in the program ``jitted(*args)`` lowers
    to — the evidence that a Pallas kernel is in the program."""
    return jitted.lower(*args).as_text().count("tpu_custom_call")


# ---------------------------------------------------------------------------
# ring: resnet50 through Defer / SpmdPipeline, buffer wire then int8 wire
# ---------------------------------------------------------------------------

def _ring_pass(run: Run, ph: "Phase", graph, params, x, *,
               num_stages: int, wire: str = "buffer"):
    """Build the ring through ``Defer``, compile it, stream ``x``; notes
    the times and where the shards sit.  Returns the output block."""
    import jax

    from defer_tpu import Defer, DeferConfig
    from defer_tpu.utils.xla_opts import ring_jit_kwargs

    sz = run.sizes
    pipe = Defer(config=DeferConfig(
        microbatch=sz.microbatch, chunk=sz.chunk, buffer_dtype="bfloat16",
        compute_dtype="bfloat16", wire=wire)).build(
            graph, params, num_stages=num_stages)
    ph.note(ring_compile_options=ring_jit_kwargs(
        pipe.mesh.devices).get("compiler_options", {}))
    if wire == "int8":
        kernels = lowered_kernel_count(
            pipe._chunk_fn, pipe._w, pipe._a, pipe._bubble_block())
        ph.note(mosaic_kernels_in_chunk_program=kernels)
        if sz.expect_mosaic and kernels < 1:
            raise AssertionError(
                "the int8-wire chunk program holds no TPU custom call: "
                "the Pallas quantiser was not selected")
    t0 = time.perf_counter()
    pipe.warmup()  # compile + one all-bubble chunk
    jax.block_until_ready(pipe._a)
    ph.note(first_call_s=round(time.perf_counter() - t0, 3))
    t0 = time.perf_counter()
    out = pipe.run(x)
    ph.note(run_s=round(time.perf_counter() - t0, 3),
            images=int(x.shape[0] * x.shape[1]))
    w_ids, a_ids = shard_device_ids(pipe._w), shard_device_ids(pipe._a)
    ph.note(weight_shard_device_ids=w_ids,
            activation_shard_device_ids=a_ids)
    if len(set(w_ids)) != num_stages or len(set(a_ids)) != num_stages:
        raise AssertionError(
            f"{num_stages} stages but weights on devices {w_ids}, "
            f"activations on {a_ids}")
    if not np.isfinite(out).all():
        raise AssertionError("ring output holds non-finite values")
    return out


def phase_ring(run: Run) -> None:
    import jax
    import jax.numpy as jnp

    sz, n = run.sizes, run.n
    graph, params = run.model(sz.cnn, 0)
    x = np.random.default_rng(0).standard_normal(
        (sz.chunk * sz.chunks, sz.microbatch)
        + graph.input_spec.shape).astype(np.float32)

    with run.phase("ring.reference") as ph:
        fwd = jax.jit(graph.apply)
        t0 = time.perf_counter()
        ref_f32 = np.stack([np.asarray(fwd(params, xi)) for xi in x])
        ph.note(first_call_s=round(time.perf_counter() - t0, 3))
        params_bf = jax.tree.map(
            lambda a: jnp.asarray(a, jnp.bfloat16), params)
        ref_bf = np.stack([np.asarray(
            fwd(params_bf, jnp.asarray(xi, jnp.bfloat16)), np.float32)
            for xi in x])
        ph.check("bf16_program_vs_f32_program", rel_err(ref_bf, ref_f32),
                 TOL["ring_vs_f32_program"])
    run.sync_rtt("after_first_large_executable")

    with run.phase("ring.buffer") as ph:
        out = _ring_pass(run, ph, graph, params, x, num_stages=n)
        if out.shape != ref_f32.shape:
            raise AssertionError(f"ring output shape {out.shape}")
        ph.check("rel_err_vs_bf16_program", rel_err(out, ref_bf),
                 TOL["ring_vs_bf16_program"])
        ph.check("rel_err_vs_f32_program", rel_err(out, ref_f32),
                 TOL["ring_vs_f32_program"])
    with run.phase("ring.int8") as ph:
        out_q = _ring_pass(run, ph, graph, params, x, num_stages=n,
                           wire="int8")
        ph.check("rel_logit_err_vs_f32_program", rel_err(out_q, ref_f32),
                 TOL["int8_rel_logit_err"])
    if n > 1:
        with run.phase("ring.1_stage") as ph:
            out1 = _ring_pass(run, ph, graph, params, x, num_stages=1)
            ph.check(f"rel_err_{n}_stage_vs_1_stage", rel_err(out, out1),
                     TOL["n_stage_vs_1_stage"])


# ---------------------------------------------------------------------------
# serve, tensor mode: the `serve` command's deployment, two tenants
# ---------------------------------------------------------------------------

def _serve_args(*argv: str):
    from defer_tpu import cli
    return cli.build_parser().parse_args(
        ["serve", "--listen", "127.0.0.1:0", *argv])


def _stream_tenants(addr, per_tenant: dict, **hello) -> dict:
    """One ``ServeClient`` per tenant, concurrently; ``{tenant:
    [outcome, ...]}`` in send order.  A client failure fails the run."""
    from defer_tpu.serve.client import ServeClient
    host, port = addr
    outs: dict = {}
    errs: list = []

    def one(tenant: str) -> None:
        try:
            c = ServeClient(host, port, tenant, timeout_s=600.0,
                            **hello.get(tenant, {}))
            outs[tenant] = c.stream(per_tenant[tenant])
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errs.append(e)

    threads = [threading.Thread(target=one, args=(t,)) for t in per_tenant]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=900)
    if errs:
        raise errs[0]
    if any(th.is_alive() for th in threads):
        raise TimeoutError("a serve client did not finish in 900 s")
    return outs


def phase_serve_tensor(run: Run) -> None:
    import jax

    from defer_tpu import cli

    sz = run.sizes
    graph, params = run.model(sz.cnn, 0)  # serve_deployment's seed too
    rng = np.random.default_rng(1)
    in_shape = graph.input_spec.shape
    data = {t: [rng.standard_normal(in_shape).astype(np.float32)
                for _ in range(sz.serve_requests)]
            for t in ("alpha", "beta")}

    with run.phase("serve.tensor") as ph:
        solo = jax.jit(graph.apply)
        refs = {t: [np.asarray(solo(params, s[None]))[0] for s in xs]
                for t, xs in data.items()}
        args = _serve_args("--model", sz.cnn, "--stages",
                           str(sz.serve_stages), "--width",
                           str(sz.serve_width), "--tenant", "alpha=1",
                           "--tenant", "beta=1")
        door, disp, addrs, cleanup = cli.serve_deployment(args)
        door.start()
        try:
            t0 = time.perf_counter()
            warm = _stream_tenants(door.address,
                                   {"warmup": data["alpha"][:1]})
            ph.note(first_call_s=round(time.perf_counter() - t0, 3))
            if warm["warmup"][0][0] != "ok":
                raise AssertionError(f"warm-up request: {warm}")
            t0 = time.perf_counter()
            got = _stream_tenants(door.address, data)
            ph.note(run_s=round(time.perf_counter() - t0, 3),
                    requests=2 * sz.serve_requests)
            door.healthcheck()
            node_stats = disp.stats(addrs)
            door_stats = door.stats()
        finally:
            door.stop()
            cleanup()
        worst = 0.0
        for t, outcomes in got.items():
            for i, oc in enumerate(outcomes):
                if oc is None or oc[0] != "ok":
                    raise AssertionError(
                        f"tenant {t} request {i} was not answered: {oc}")
                worst = max(worst, rel_err(oc[1], refs[t][i]))
        ph.check("rel_err_vs_solo_forward", worst, TOL["serve_vs_solo"])
        ph.note(shed=door_stats["shed"], admitted=door_stats["admitted"])
        if door_stats["shed"]:
            raise AssertionError(f"{door_stats['shed']} request(s) shed")
        # the deployment spreads stages over every device of the process
        devs = jax.devices()
        stage_devs = [{"stage": st["stage"],
                       "weights": st["weight_device_ids"],
                       "outputs": st["output_device_ids"],
                       "processed": st["processed"]} for st in node_stats]
        ph.note(stage_devices=stage_devs)
        for k, sd in enumerate(stage_devs):
            want = [devs[k % len(devs)].id]
            if sd["weights"] != want or sd["outputs"] != want:
                raise AssertionError(
                    f"stage {k} should sit on device {want}: {sd}")
        if len({tuple(sd["weights"]) for sd in stage_devs}) \
                != min(len(devs), sz.serve_stages):
            raise AssertionError(f"stages not spread: {stage_devs}")


# ---------------------------------------------------------------------------
# decode: PipelinedDecoder (prefill + decode-rate) vs a greedy reference
# ---------------------------------------------------------------------------

def phase_decode(run: Run) -> None:
    import jax
    import jax.numpy as jnp

    from defer_tpu import PipelinedDecoder, pipeline_mesh
    from defer_tpu.obs import REGISTRY

    sz, n = run.sizes, run.n
    graph, params = run.model(sz.lm, 2)
    vocab = graph.nodes["lm_head"].out_spec.shape[-1]
    seq_len = graph.input_spec.shape[0]
    b, plen, new = n * sz.lm_microbatch, sz.prompt_len, sz.new_tokens
    prompts = np.random.default_rng(2).integers(
        0, vocab, (b, plen)).astype(np.int32)

    # greedy decoding compares argmaxes, and a random-init model's top-2
    # logit gap is small: under the TPU's default f32 matmul (bf16
    # passes) two correct programs can pick different near-ties.  True
    # f32 products make token-for-token equality a fair demand.
    with jax.default_matmul_precision("highest"), \
            run.phase("decode.pipelined") as ph:
        dec = PipelinedDecoder(graph, params, num_stages=n,
                               microbatch=sz.lm_microbatch,
                               max_len=sz.lm_max_len,
                               mesh=pipeline_mesh(n))
        # the cut the bytes chose, from the decoder's own gauges
        ph.note(cut=[int(REGISTRY.gauge(f"decode.cut.blocks.{s}").value)
                     for s in range(n)])
        t0 = time.perf_counter()
        toks_pre = dec.generate(prompts, new, prefill=True)
        ph.note(first_call_s=round(time.perf_counter() - t0, 3))
        t0 = time.perf_counter()
        toks_pre2 = dec.generate(prompts, new, prefill=True)
        ph.note(run_s=round(time.perf_counter() - t0, 3),
                tokens=int(b * new))
        toks_rate = dec.generate(prompts, new)

        pfn = dec._prefill_fns[(plen, False, None)]
        _a, caches = dec._init_state()
        kernels = lowered_kernel_count(
            pfn, dec._w, jnp.zeros((n, sz.lm_microbatch, plen), jnp.int32),
            jnp.uint32(0), jnp.float32(0.0), caches)
        # the weights are a tree since the GPT family's nodes name their
        # leaves stage-sharded arguments of their own: every leaf is
        # sharded alike, so one stands for all
        w_ids = shard_device_ids(jax.tree.leaves(dec._w)[0])
        ph.note(mosaic_kernels_in_prefill_program=kernels,
                weight_shard_device_ids=w_ids,
                cache_shard_device_ids=shard_device_ids(caches["k"][0]))
        if sz.expect_mosaic and kernels < 1:
            raise AssertionError(
                "the prefill program holds no TPU custom call: flash "
                "attention was not selected")
        if len(set(w_ids)) != n:
            raise AssertionError("decoder weights not on n devices")

        # single-program greedy reference: the whole-graph forward on a
        # padded buffer (causal: position t-1 sees only tokens < t)
        @jax.jit
        def next_ids(p, ids, t):
            logits = graph.apply(p, ids)
            return jnp.argmax(jnp.take(logits, t - 1, axis=1), axis=-1)

        buf = np.zeros((b, seq_len), np.int32)
        buf[:, :plen] = prompts
        for t in range(plen, plen + new):
            buf[:, t] = np.asarray(next_ids(params, jnp.asarray(buf),
                                            jnp.int32(t)))
        ref = buf[:, :plen + new]

        for what, toks in (("prefill", toks_pre), ("prefill_again",
                                                   toks_pre2),
                           ("decode_rate", toks_rate)):
            if not np.array_equal(toks, ref):
                bad = np.argwhere(toks != ref)
                raise AssertionError(
                    f"decode.pipelined: {what} tokens differ from the "
                    f"single-program greedy reference at (row, pos) "
                    f"{bad[:5].tolist()} of {len(bad)}")
        ph.note(token_for_token="prefill == decode-rate == "
                                "single-program reference")


# ---------------------------------------------------------------------------
# serve, decode workload: ContinuousBatchEngine behind the front door
# ---------------------------------------------------------------------------

def phase_serve_decode(run: Run) -> None:
    import jax

    from defer_tpu import cli

    sz, n = run.sizes, run.n
    graph, _params = run.model(sz.lm, 2)
    vocab = graph.nodes["lm_head"].out_spec.shape[-1]
    rng = np.random.default_rng(3)
    # more requests than slots, unequal lengths: the short ones leave
    # mid-stream and the waiting ones join in their place
    reqs = {}
    for i in range(sz.engine_width + 2):
        reqs[f"r{i}"] = (
            rng.integers(0, vocab, (3 + i % 4,)).astype(np.int32),
            max(2, sz.new_tokens - 3 * (i % 3)))

    with run.phase("serve.decode") as ph:
        args = _serve_args("--workload", "decode", "--model", sz.lm,
                           "--stages", str(n), "--width",
                           str(sz.engine_width), "--max-new",
                           str(sz.new_tokens))
        door, _disp, _addrs, cleanup = cli.serve_deployment(args)
        door.start()
        try:
            hello = {t: {"max_new_tokens": mn} for t, (_p, mn)
                     in reqs.items()}
            t0 = time.perf_counter()
            _stream_tenants(door.address, {"r0": [reqs["r0"][0]]},
                            r0=hello["r0"])
            ph.note(first_call_s=round(time.perf_counter() - t0, 3))
            t0 = time.perf_counter()
            together = _stream_tenants(
                door.address, {t: [p] for t, (p, _mn) in reqs.items()},
                **hello)
            ph.note(run_s=round(time.perf_counter() - t0, 3),
                    requests=len(reqs),
                    tokens=int(sum(mn for _p, mn in reqs.values())))
            alone = {}
            for t, (p, _mn) in reqs.items():  # one at a time
                alone.update(_stream_tenants(door.address, {t: [p]},
                                             **{t: hello[t]}))
            door.healthcheck()
            stats = door.stats()
            dev_ids = sorted({d.id for leaf in jax.tree.leaves(
                door.engine.params) for d in leaf.devices()})
        finally:
            door.stop()
            cleanup()
        for t, (p, mn) in reqs.items():
            a, s = together[t][0], alone[t][0]
            if a is None or s is None or a[0] != "ok" or s[0] != "ok":
                raise AssertionError(f"request {t}: {a} / {s}")
            if a[1].shape != (p.size + mn,) \
                    or not np.array_equal(a[1], s[1]):
                raise AssertionError(
                    f"request {t}: batched output differs from the same "
                    f"request run alone")
        if stats["shed"]:
            raise AssertionError(f"{stats['shed']} request(s) shed")
        ph.note(engine_steps=stats["decode"]["steps"],
                shed=stats["shed"], compute_device_ids=dev_ids,
                note=f"serve --workload decode computes on ONE chip "
                     f"(device {dev_ids}) whatever --stages says "
                     f"(here --stages {n})")
        if len(dev_ids) != 1:
            raise AssertionError(
                f"the decode engine's weights sit on {dev_ids}; the "
                f"one-chip statement above is no longer true")


# ---------------------------------------------------------------------------
# export: a transformer stage artifact round trip (flash rides inside)
# ---------------------------------------------------------------------------

def phase_export(run: Run) -> None:
    import jax

    from defer_tpu import partition
    from defer_tpu.utils.export import export_stage_bytes, load_stage_program

    sz = run.sizes
    graph, params = run.model(sz.lm, 2)
    stage = partition(graph, num_stages=2)[1]  # blocks + final_ln + head
    x = np.random.default_rng(4).standard_normal(
        (2,) + stage.in_spec.shape).astype(np.float32)

    with run.phase("export.transformer_stage") as ph:
        prog = load_stage_program(export_stage_bytes(stage, params,
                                                     batch=2))
        kernels = lowered_kernel_count(prog._call, prog._leaves, x)
        ph.note(mosaic_kernels_in_artifact=kernels,
                weight_device_ids=prog.weight_device_ids)
        if sz.expect_mosaic and kernels < 1:
            raise AssertionError(
                "the exported transformer stage holds no TPU custom call")
        t0 = time.perf_counter()
        got = np.asarray(prog(x))
        ph.note(first_call_s=round(time.perf_counter() - t0, 3))
        t0 = time.perf_counter()
        got = np.asarray(prog(x))
        ph.note(run_s=round(time.perf_counter() - t0, 4))
        direct = np.asarray(jax.jit(stage.fn)(stage.select_params(params),
                                              x))
        ph.check("rel_err_vs_direct_jit", rel_err(got, direct),
                 TOL["export_round_trip"])


def run_phases(sizes: Sizes, devices) -> dict:
    """Every phase, in order; the first failure raises."""
    from defer_tpu.utils import compile_cache
    run = Run(sizes, devices)
    cache_dir = compile_cache.configure()
    say(f"compile cache: {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'in-checkout default'})")
    run.sync_rtt("before_first_large_executable")
    phase_ring(run)
    phase_serve_tensor(run)
    phase_decode(run)
    phase_serve_decode(run)
    phase_export(run)
    run.sync_rtt("end_of_run")
    return run.report


def main() -> int:
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    say(f"chip_smoke: platform={d0.platform} "
        f"device_kind={d0.device_kind!r} count={len(devices)}")
    if d0.platform != "tpu":
        print(f"chip_smoke: FAIL: this smoke needs platform=tpu; jax "
              f"found {len(devices)} x {d0.platform}", file=sys.stderr)
        return 2
    from defer_tpu.utils.hw import detect_chip
    gen = detect_chip(d0)  # raises on a kind utils/hw.py does not know
    if len(devices) not in (1, 4):
        print(f"chip_smoke: FAIL: expected 1 or 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    say(f"chip_smoke: generation {gen}, {len(devices)} stage(s)")

    report = run_phases(FULL, devices)
    report.update(device=device, generation=gen,
                  total_s=round(time.perf_counter() - t_start, 2))
    out_dir = "chiprun_out"
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"chip_smoke_{len(devices)}chip_"
                           f"{int(time.time())}.json"), "w") as f:
        json.dump(report, f, indent=1)
    say(f"chip_smoke: all phases passed in {report['total_s']} s "
        f"(compile {sum(p['compile_s'] for p in report['phases'].values()):.1f} s "
        f"over {sum(p['compiles'] for p in report['phases'].values())} "
        f"compiles, {sum(p['cache_hits'] for p in report['phases'].values())} "
        f"persistent-cache hits)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
