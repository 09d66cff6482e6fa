#!/usr/bin/env python3
"""Run the deep-copy runtime's serving path once on a TPU at the published
widths of mamba2-1.3b (48 layers, d_model 2048, ssm_state 128, vocab
50280, bf16 params from a seed), and check what comes out.

    python chip_smoke.py              # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4    # four chips: the @dp4 path only
    python chip_smoke.py --arch moonlight-16b-a3b-s9   # one chip: phase (m)

Everything runs in this one process, which holds the chip.

(a) Deep copy at real size.  ``serve_transfer_policy(1)`` compiled for the
    full-width ServeState.  One cold ``to_device`` moves exactly the bytes
    the arena plan derives, ``from_device`` gives back the host tree byte
    for byte, and after one cache leaf changes a steady pass re-ships only
    that leaf's dtype bucket of the cache region.
(b) Serving through ``repro.launch.serve.serve``: 8 slots, 8 requests,
    prompts of 4-15 tokens, 16 new tokens each.  Every request completes,
    none is shed, timed out or failed, and the server staged its state
    under the requested policy (no degradation).  For one request, the
    logits of prefill then decode through the cache are compared with one
    prefill of the whole sequence from a fresh cache.

``--chips 4`` stages the ServeState under ``serve_transfer_policy(4)``
(params in a 4-way sharded arena), checks the per-device bytes against the
arena plan and the round trip, serves the same requests at ``--dp 4``, and
compares their tokens with a one-device staging of the same tree.

``--arch moonlight-16b-a3b-s9`` runs phase (m) instead, at the widths of
``bench/configs/moonlight-16b-a3b-s9.json`` (Moonlight-16B-A3B, one
chip's share: 9 layers, 8 of 64 experts, a 20,480-row vocabulary) with
the benchmark's seeded bf16 weights (``bench/families/moe.py``):

(m) A 16-slot, 8,192-position ServeState staged through
    ``serve_transfer_policy(1)``; an 8,000-token prompt (ids from the
    vocabulary slice) prefilled into slot 3 and installed as the server
    installs a refill; slot 3's rows read back to the host as a saved
    session, written into slot 11 of the host tree, ``mark_dirty`` and
    ``to_device`` (the resume op of ``moonlight-16b-a3b-s9.resume-8k``);
    the resumed rows on the device equal the saved ones byte for byte;
    16 tokens decoded from slot 11.  The logits of the prefill and of
    each decode step are compared with the plain reference
    (``bench/reference/deepseek_v3.py``, float32, highest precision, in
    query blocks) over prompt + tokens, in bf16 as the server runs, and
    again with the program at float32 (prefill and decode on a one-slot
    cache).

Any failed check raises and the script exits 1.  Without a TPU (for
example under ``JAX_PLATFORMS=cpu``) it exits 1 naming the platform it
found.  The last line of standard output, on success only, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
phase (m) adds ``"moonlight"``: the logit errors against the reference
and the peak device memory.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "mamba2-1.3b"
SLOTS, REQUESTS, MAX_NEW, MAX_SEQ = 8, 8, 16, 128
SERVE_ARGV = ["--arch", ARCH, "--slots", str(SLOTS), "--requests",
              str(REQUESTS), "--max-new", str(MAX_NEW), "--max-seq",
              str(MAX_SEQ)]

# Logit agreement of prefill-then-decode through the cache against one
# prefill of the whole sequence, as ||a - b|| / ||b||.  The two paths sum in
# different orders (the chunked SSD scan over the sequence against the
# per-token recurrence), and with random weights each layer amplifies a
# difference by about 1.2x, so 48 layers turn roundoff into error:
#  * f32 compute, full depth, highest matmul precision: f32 roundoff grows
#    to 2e-3 (d_model 256) and 6e-4 (d_model 512) on the CPU backend; a 1%
#    error in the cached SSM state gives 0.7.  Bound 1e-2.
#  * bf16 compute (what the server runs), depth cut to 4 layers: bf16
#    roundoff (2^-8) grows to 1.1e-2 on the CPU backend.  Bound 5e-2.
#  * bf16 at full depth is printed, not checked: there the roundoff grows
#    to 0.15-0.8, as large as a wrong cache.
TOL_F32_FULL_DEPTH = 1e-2
TOL_BF16_DEPTH4 = 5e-2
BF16_CHECK_LAYERS = 4

MOONLIGHT = "moonlight-16b-a3b-s9"
ML_SLOTS, ML_MAX_SEQ, ML_PROMPT, ML_DECODE = 16, 8192, 8000, 16
ML_FROM, ML_TO = 3, 11          # the slot prefilled, the slot resumed into
ML_BLOCK_Q = 512                # the reference's query blocks
# Phase (m), logits of each compared position (the prompt's last, then the
# 16 decoded) against the reference, as ||a - b|| / ||b||:
#  * float32 program, highest matmul precision: the two differ by summation
#    order only, 1.2e-6 at d_model 1024 and 9 layers on the CPU backend;
#    rounding attention's inputs to bf16 moves them by ~1e-2 (8e-2 at tiny
#    widths), the experts' by 1e-3.  Bound 1e-3 on every position.
#  * bf16 as the server runs: roundoff gives ~1e-2 a position at d_model
#    1024 on the CPU backend, and where bf16 flips an expert choice near a
#    tie one position reads ~0.1; decoding the same tokens after another
#    session reads 1.1-1.5.  Bound 0.1 on the median of the 17 positions.
TOL_ML_F32 = 1e-3
TOL_ML_BF16_MEDIAN = 0.1


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class CompileClock:
    """Counts backend compilations and their seconds (jax.monitoring)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == self.EVENT:
            self.count += 1
            self.seconds += secs

    def take(self):
        out = (self.count, self.seconds)
        self.count, self.seconds = 0, 0.0
        return out


def peak_bytes(devices) -> str:
    return ", ".join(
        f"dev{d.id} {(d.memory_stats() or {}).get('peak_bytes_in_use', 'n/a')}"
        for d in devices)


def same_bytes(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(np.ascontiguousarray(a).view(np.uint8),
                               np.ascontiguousarray(b).view(np.uint8)))


def jax_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def trees_same_bytes(a, b) -> bool:
    import jax

    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(same_bytes(x, y) for x, y in zip(la, lb))


def host_serve_state(api):
    """The full-width host ServeState: params from the seed, and a cache
    and slot table filled with seeded random bytes so the round trip
    compares real data, not zeros."""
    import jax
    import numpy as np
    from repro.launch.serve import SEED
    from repro.runtime.serve import serve_state

    host = serve_state(api, api.init(jax.random.PRNGKey(SEED)), SLOTS,
                       MAX_SEQ)
    rng = np.random.default_rng(SEED)
    for key, val in host["cache"].items():
        if np.issubdtype(val.dtype, np.integer):
            host["cache"][key] = rng.integers(0, MAX_SEQ, val.shape,
                                              dtype=val.dtype)
        else:
            host["cache"][key] = rng.standard_normal(
                val.shape, dtype=np.float32).astype(val.dtype)
    host["slots"]["rid"] = np.arange(SLOTS, dtype=np.int32)
    return host


def expected_h2d_by_device(host, policy, program):
    """Per-device H2D bytes of one cold pass, from the arena plan of every
    region (``derive_policy_motion``): a sharded region puts its per-device
    share on every device of its mesh, the others put all on their
    device."""
    from repro.scenarios.base import derive_policy_motion

    out = {}
    for key, motion in derive_policy_motion(host, policy).items():
        scheme = program.scheme(key)
        if motion.per_device_bytes is None:
            devs, nb = [scheme.device], motion.h2d_bytes
        else:
            devs, nb = list(scheme.sharding.mesh.devices.flat), \
                motion.per_device_bytes
        for d in devs:
            out[str(d.id)] = out.get(str(d.id), 0) + nb
    return out


def stage_and_round_trip(phase, host, policy, session):
    """Cold pass (per-device bytes against the plan) and a byte-exact
    ``from_device`` round trip.  Returns the program."""
    t0 = time.perf_counter()
    program = session.compile(host, policy)
    t1 = time.perf_counter()
    dev = program.to_device(host)
    t2 = time.perf_counter()
    ledger = program.merged_ledger()
    want = expected_h2d_by_device(host, policy, program)
    say(phase, f"policy {policy}")
    say(phase, f"cold to_device: h2d {ledger.h2d_bytes} B in "
               f"{ledger.h2d_calls} calls, by device "
               f"{dict(sorted(ledger.h2d_bytes_by_device.items()))}, "
               f"plan {dict(sorted(want.items()))}; compile "
               f"{t1 - t0:.3f} s, pass wall {t2 - t1:.3f} s (informational)")
    check(ledger.h2d_bytes_by_device == want,
          f"cold per-device h2d bytes {ledger.h2d_bytes_by_device} != "
          f"arena plan {want}")
    check(program.last_stats.syncs == 1, "cold pass did not sync once")
    t3 = time.perf_counter()
    back = program.from_device(dev, host)
    t4 = time.perf_counter()
    check(trees_same_bytes(back, host),
          "from_device round trip is not byte-exact")
    say(phase, f"from_device round trip byte-exact over "
               f"{len(jax_leaves(host))} leaves; wall {t4 - t3:.3f} s "
               f"(informational)")
    return program


def phase_deep_copy(api, devices, clock) -> None:
    import numpy as np
    from repro.core.engine import TransferSession
    from repro.runtime import serve_transfer_policy
    from repro.scenarios.base import derive_steady_policy_motion

    host = host_serve_state(api)
    sizes = {part: sum(int(np.asarray(l).nbytes) for l in jax_leaves(sub))
             for part, sub in host.items()}
    say("a", f"{ARCH} ServeState host bytes {sizes} "
             f"(total {sum(sizes.values())})")
    session = TransferSession()
    policy = serve_transfer_policy(1)
    program = stage_and_round_trip("a", host, policy, session)

    # one cache leaf changes by one bit: the steady pass re-ships only the
    # cache region's bucket holding it
    mutated = "cache.conv"
    conv = host["cache"]["conv"].copy()
    conv.reshape(-1).view(np.uint8)[0] ^= 1
    host["cache"] = dict(host["cache"], conv=conv)
    program.reset_ledgers()
    t0 = time.perf_counter()
    dev = program.to_device(host)
    wall = time.perf_counter() - t0
    cache = program.region_ledger("cache/**")
    want = derive_steady_policy_motion(host, policy, [mutated])["cache/**"]
    full = sum(program.scheme("cache/**").layout.bucket_bytes().values())
    say("a", f"steady pass after mutating {mutated}: cache region h2d "
             f"{cache.h2d_bytes} B in {cache.h2d_calls} calls, skipped "
             f"{cache.skipped_bytes} B, full {full} B, plan "
             f"{want.h2d_bytes} B in {want.h2d_calls} calls; whole pass "
             f"h2d {program.merged_ledger().h2d_bytes} B, wall {wall:.3f} s "
             f"(informational)")
    check((cache.h2d_bytes, cache.h2d_calls) == want.as_tuple(),
          "steady cache motion differs from the plan")
    check(cache.h2d_bytes + cache.skipped_bytes == full,
          "cache region h2d + skipped != full bytes")
    check(0 < cache.h2d_bytes < full, "steady pass re-shipped the whole cache")
    check(same_bytes(dev["cache"]["conv"], conv),
          "mutated cache leaf on the device differs from the host")
    n, s = clock.take()
    say("a", f"compiles {n} ({s:.3f} s); peak bytes in use "
             f"{peak_bytes(devices)}")
    program.clear()
    session.clear()


def cache_vs_prefill(api, params, prompt, tokens):
    """||a - b|| / ||b|| of the last logits of (a) prefill(prompt) then one
    decode step per token of ``tokens`` through the cache and (b) one
    prefill of prompt + tokens from a fresh cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    prefill, decode = jax.jit(api.prefill), jax.jit(api.decode_step)
    cache = api.init_cache(1, MAX_SEQ)
    logits, cache = prefill(params, jnp.asarray(prompt)[None], cache)
    for tok in tokens:
        logits, cache = decode(params, jnp.asarray([[tok]], jnp.int32), cache)
    a = np.asarray(logits, np.float32).reshape(-1)
    seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    ref, _ = prefill(params, jnp.asarray(seq)[None],
                     api.init_cache(1, MAX_SEQ))
    b = np.asarray(ref, np.float32).reshape(-1)
    check(bool(np.isfinite(a).all() and np.isfinite(b).all()),
          "non-finite logits")
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_served(done, server, dp: int) -> None:
    from repro.runtime import serve_transfer_policy
    from repro.runtime.admission import COMPLETED

    stats = server.stats
    check(len(done) == REQUESTS and all(r.state == COMPLETED for r in done),
          f"not every request completed: {[(r.rid, r.state) for r in done]}")
    check((stats.completed, stats.shed, stats.timed_out, stats.failed)
          == (REQUESTS, 0, 0, 0), f"serve stats {stats}")
    check(stats.policy_fallbacks == 0 and not stats.degradations,
          f"server degraded its placement: {stats.degradations}")
    check(str(server.policy) == str(serve_transfer_policy(dp)),
          f"server staged under {server.policy}")
    check(all(len(r.tokens_out) == MAX_NEW for r in done),
          "a request ended before its max_new tokens")


def phase_serve(api, devices, clock) -> None:
    import dataclasses

    import jax
    from repro.launch.serve import build_parser, serve
    from repro.models import registry

    t0 = time.perf_counter()
    done, server = serve(build_parser().parse_args(SERVE_ARGV))
    wall = time.perf_counter() - t0
    n, s = clock.take()
    stats = server.stats
    tokens = sum(len(r.tokens_out) for r in done)
    say("b", f"served {len(done)}/{REQUESTS} requests, {tokens} tokens; "
             f"completed {stats.completed} shed {stats.shed} timed-out "
             f"{stats.timed_out} failed {stats.failed} policy-fallbacks "
             f"{stats.policy_fallbacks}; policy {server.policy}")
    say("b", f"serve wall {wall:.3f} s including set-up and {n} compiles "
             f"({s:.3f} s) (informational); peak bytes in use "
             f"{peak_bytes(devices)}")
    check_served(done, server, 1)

    req = min(done, key=lambda r: r.rid)
    prompt, toks = req.prompt, req.tokens_out[:-1]
    cfg = api.cfg
    with jax.default_matmul_precision("highest"):
        err32 = cache_vs_prefill(
            registry.get_model(dataclasses.replace(
                cfg, compute_dtype="float32")),
            server.params, prompt, toks)
    depth = min(BF16_CHECK_LAYERS, cfg.num_layers)
    params_cut = dict(server.params, blocks=jax.tree_util.tree_map(
        lambda a: a[:depth], server.params["blocks"]))
    err16 = cache_vs_prefill(
        registry.get_model(dataclasses.replace(cfg, num_layers=depth)),
        params_cut, prompt, toks)
    err16_full = cache_vs_prefill(api, server.params, prompt, toks)
    say("b", f"request {req.rid} (prompt {len(prompt)} + {len(toks)} decode "
             f"steps): logits rel. error f32 full depth {err32:.3e} "
             f"(bound {TOL_F32_FULL_DEPTH:g}), bf16 {depth} layers "
             f"{err16:.3e} (bound {TOL_BF16_DEPTH4:g}), bf16 full depth "
             f"{err16_full:.3e} (not checked)")
    check(err32 <= TOL_F32_FULL_DEPTH, "f32 logits disagree")
    check(err16 <= TOL_BF16_DEPTH4, "bf16 logits disagree")
    n, s = clock.take()
    say("b", f"logit checks: {n} compiles ({s:.3f} s); peak bytes in use "
             f"{peak_bytes(devices)}")


def phase_dp4(api, devices, clock) -> None:
    from repro.core.engine import TransferSession
    from repro.launch.serve import build_parser, serve
    from repro.runtime import serve_transfer_policy

    host = host_serve_state(api)
    session = TransferSession()
    program = stage_and_round_trip("dp4", host, serve_transfer_policy(4),
                                   session)
    del host
    program.clear()
    session.clear()

    tokens = {}
    for dp in (4, 1):
        done, server = serve(build_parser().parse_args(
            SERVE_ARGV + ["--dp", str(dp)]))
        check_served(done, server, dp)
        tokens[dp] = {r.rid: list(r.tokens_out) for r in done}
        say("dp4", f"--dp {dp}: served {len(done)} requests, policy "
                   f"{server.policy}, policy-fallbacks "
                   f"{server.stats.policy_fallbacks}")
        del done, server
        gc.collect()
    same = sum(tokens[4][rid] == tokens[1][rid] for rid in tokens[1])
    say("dp4", f"tokens equal to the one-device staging for {same}/"
               f"{len(tokens[1])} requests")
    check(same == len(tokens[1]), "dp4 tokens differ from one device")
    n, s = clock.take()
    say("dp4", f"compiles {n} ({s:.3f} s); peak bytes in use "
               f"{peak_bytes(devices)}")


def _rel_errors(got, want):
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          "non-finite logits")
    return [float(np.linalg.norm(a - b) / np.linalg.norm(b))
            for a, b in zip(got, want)]


def _decode_from(api, params, cache, slot, tokens):
    """Decode ``tokens`` one at a time from ``slot`` of ``cache`` (the other
    slots decode zeros); the slot's logits of each step."""
    import jax
    import jax.numpy as jnp

    decode = jax.jit(api.decode_step)
    n = cache["pos"].shape[0]
    out = []
    for tok in tokens:
        toks = jnp.zeros((n, 1), jnp.int32).at[slot, 0].set(int(tok))
        logits, cache = decode(params, toks, cache)
        out.append(logits[slot, 0])
    return jax.device_get(out)


def phase_moonlight(devices, clock, cfg=None) -> dict:
    """Phase (m): resume an 8k-token latent session into a slot of the
    s9 ServeState and decode from it, against the reference.  ``cfg``:
    the configuration as a dict (default: the s9 file)."""
    import json
    from pathlib import Path

    import jax
    import numpy as np
    from bench import harness, weights
    from bench.model import check_params, model_api
    from bench.reference import deepseek_v3 as ref
    from repro.core.engine import TransferSession
    from repro.launch.serve import SEED
    from repro.runtime import serve_transfer_policy
    from repro.runtime.serve import serve_state

    if cfg is None:
        cfg = json.loads((Path(ROOT) / "bench" / "configs"
                          / f"{MOONLIGHT}.json").read_text())
    family = harness.load_family(Path(ROOT), cfg["family"])
    api = model_api(cfg)
    params = weights.make_params(family, cfg, SEED)
    check_params(api, params)
    rng = np.random.default_rng(SEED)
    ids = rng.integers(0, cfg["vocab_size"], ML_PROMPT + ML_DECODE,
                       dtype=np.int32)
    prompt, follow = ids[:ML_PROMPT], ids[ML_PROMPT:]

    # the ServeState through the serving policy
    # writable: the resume op writes a slot of it in place
    host = jax.tree_util.tree_map(
        np.array, serve_state(api, params, ML_SLOTS, ML_MAX_SEQ))
    del params
    session = TransferSession()
    program = session.compile(host, serve_transfer_policy(1))
    dev = jax.block_until_ready(program.to_device(host))
    nbytes = sum(int(np.asarray(leaf).nbytes) for leaf in jax_leaves(host))
    say("m", f"{cfg['name']}: ServeState {nbytes} B staged under "
             f"{serve_transfer_policy(1)}; peak bytes in use "
             f"{peak_bytes(devices)}")

    # prefill into ML_FROM as the server installs a refill, then save it
    prefill = jax.jit(api.prefill)
    logits, one = prefill(dev["params"], jax.numpy.asarray(prompt)[None],
                          api.init_cache(1, ML_MAX_SEQ))
    prefill_logits = jax.device_get(logits[0, -1])
    cache = {k: (v.at[ML_FROM].set(one[k][0]) if k == "pos"
                 else v.at[:, ML_FROM].set(one[k][:, 0]))
             for k, v in dev["cache"].items()}
    saved = {k: np.asarray(jax.device_get(v[ML_FROM] if k == "pos"
                                          else v[:, ML_FROM]))
             for k, v in cache.items()}
    del cache, one, dev

    # the resume op: write the session into ML_TO, mark_dirty, to_device
    for k, v in saved.items():
        if k == "pos":
            host["cache"][k][ML_TO] = v
        else:
            host["cache"][k][:, ML_TO] = v
    host["slots"]["rid"][ML_TO] = 0
    host["slots"]["pos"][ML_TO] = ML_PROMPT
    program.mark_dirty(host, "cache")
    program.reset_ledgers()
    t0 = time.perf_counter()
    dev = jax.block_until_ready(program.to_device(host))
    wall = time.perf_counter() - t0
    ledger = program.merged_ledger()
    check(all(same_bytes(dev["cache"][k][ML_TO] if k == "pos"
                         else dev["cache"][k][:, ML_TO], v)
              for k, v in saved.items()),
          "the resumed slot's rows differ from the saved session")
    check(int(saved["pos"]) == ML_PROMPT, f"saved pos {saved['pos']}")
    say("m", f"resume pass: session {sum(v.nbytes for v in saved.values())} "
             f"B into slot {ML_TO}; h2d {ledger.h2d_bytes} B, wall "
             f"{wall:.3f} s (informational); resumed rows byte-exact")
    bf16 = [prefill_logits] + _decode_from(api, dev["params"], dev["cache"],
                                           ML_TO, follow)
    params = dev["params"]
    del dev
    program.clear()
    session.clear()
    gc.collect()
    n, s = clock.take()
    say("m", f"bf16 prefill, resume and decode: {n} compiles ({s:.3f} s); "
             f"peak bytes in use {peak_bytes(devices)}")

    with jax.default_matmul_precision("highest"):
        api32 = model_api(dict(cfg, compute_dtype="float32"))
        logits, one = jax.jit(api32.prefill)(
            params, jax.numpy.asarray(prompt)[None],
            api32.init_cache(1, ML_MAX_SEQ))
        f32 = [logits[0, -1]] + _decode_from(api32, params, one, 0, follow)
        del one
        want = ref.forward(cfg, params, ids, block_q=ML_BLOCK_Q, jit=True,
                           logits_at=range(ML_PROMPT - 1,
                                           ML_PROMPT + ML_DECODE))
    e16 = _rel_errors(bf16, want)
    e32 = _rel_errors(f32, want)
    med16 = float(np.median(e16))
    n, s = clock.take()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    out = {"prompt": ML_PROMPT, "decoded": ML_DECODE,
           "f32_max_rel_err": max(e32), "f32_prefill_rel_err": e32[0],
           "bf16_median_rel_err": med16, "bf16_max_rel_err": max(e16),
           "bf16_prefill_rel_err": e16[0], "peak_bytes_in_use": peak}
    say("m", f"logits against the reference over {ML_PROMPT} + "
             f"{ML_DECODE} tokens: f32 max {max(e32):.3e} (bound "
             f"{TOL_ML_F32:g}), bf16 median {med16:.3e} (bound "
             f"{TOL_ML_BF16_MEDIAN:g}), bf16 max {max(e16):.3e}; per "
             f"position f32 {[f'{e:.2e}' for e in e32]}, bf16 "
             f"{[f'{e:.2e}' for e in e16]}; {n} compiles ({s:.3f} s)")
    check(max(e32) <= TOL_ML_F32, "f32 logits disagree with the reference")
    check(med16 <= TOL_ML_BF16_MEDIAN,
          "bf16 logits disagree with the reference")
    return out


def run(chips: int, arch: str = ARCH) -> dict:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no repro package under {src}: run chip_smoke.py "
                           f"from a checkout of the repository")
    sys.path[:0] = [src, ROOT]
    import jax
    from repro.jaxenv import use_compile_cache
    from repro.models import registry

    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        raise SmokeFailure(f"JAX found no TPU: platform {dev0.platform!r} "
                           f"({dev0.device_kind})")
    check(len(devices) >= chips,
          f"--chips {chips} but JAX sees {len(devices)} device(s)")
    say("env", f"jax {jax.__version__}, {len(devices)} x {dev0.device_kind}, "
               f"compile cache {cache_dir}")
    clock = CompileClock()
    out = {"device": {"platform": dev0.platform, "kind": dev0.device_kind,
                      "count": len(devices)}}
    if arch == MOONLIGHT:
        check(chips == 1, f"{MOONLIGHT} runs on one chip")
        out["moonlight"] = phase_moonlight(devices[:1], clock)
        return out
    api = registry.get(ARCH)
    if chips == 4:
        phase_dp4(api, devices, clock)
    else:
        phase_deep_copy(api, devices, clock)
        gc.collect()
        phase_serve(api, devices, clock)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke.py")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the @dp4 sharded staging path and its "
                         "one-device comparison")
    ap.add_argument("--arch", choices=(ARCH, MOONLIGHT), default=ARCH,
                    help=f"{MOONLIGHT}: phase (m) only")
    args = ap.parse_args(argv)
    try:
        out = run(args.chips, args.arch)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps(dict({"ok": True}, **out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
