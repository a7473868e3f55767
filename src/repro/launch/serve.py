"""Serving driver: LM decode and the streaming-subspace query front end.

Two lanes:

  * ``python -m repro.launch.serve --arch <id> --prompt-len 64 --gen 32``
    serves the reduced LM config on the host mesh (the full configs are
    exercised via the dry-run): jitted prefill, donated-cache decode
    steps, batched requests in lockstep.  Continuous batching (ragged
    positions per row) remains scoped out of the LM lane.

  * ``python -m repro.launch.serve --subspace --queries 4096`` serves the
    *paper's own* artifact — the distributed eigenspace estimate — as a
    query endpoint (``repro.stream.SubspaceService``): a synthetic
    per-shard row stream feeds the service's accumulators, cadence-
    triggered Procrustes refreshes keep the basis fresh (previous basis
    as reference, so clients never see a sign/rotation flip), and query
    batches project onto the double-buffered served basis with zero
    collectives on the hot path (DESIGN.md §10).
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import batch_shardings, param_shardings
from repro.launch.steps import jit_decode_step
from repro.models import build, init_split

log = logging.getLogger("repro.serve")


def serve(
    arch: str,
    *,
    batch: int = 4,
    prompt_len: int = 32,
    gen: int = 16,
    reduced: bool = True,
    mesh=None,
    greedy: bool = True,
    seed: int = 0,
):
    cfg = get_reduced_config(arch) if reduced else get_config(arch)
    mesh = mesh or make_host_mesh()
    api = build(cfg)
    values, axes = init_split(cfg, jax.random.PRNGKey(seed))
    cache_len = prompt_len + gen + (cfg.num_patches or 0)

    key = jax.random.PRNGKey(seed + 1)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    pbatch = {"tokens": prompts, "cache_len": cache_len}
    if cfg.is_encoder_decoder:
        pbatch["frames"] = jax.random.normal(
            key, (batch, prompt_len, cfg.d_model), dtype=jnp.dtype(cfg.dtype)
        )
    if cfg.num_patches:
        pbatch["patch_embeds"] = jax.random.normal(
            key, (batch, cfg.num_patches, cfg.patch_embed_dim),
            dtype=jnp.dtype(cfg.dtype),
        )

    t0 = time.perf_counter()
    logits, cache = api.prefill(values, pbatch)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    decode_jit, _ = jit_decode_step(cfg, mesh, values, axes, cache)
    params = jax.device_put(values, param_shardings(values, axes, mesh, cfg))

    out_tokens = []
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    pos = prompt_len + (cfg.num_patches or 0)
    t0 = time.perf_counter()
    for i in range(gen):
        out_tokens.append(np.asarray(tok)[:, 0])
        logits, cache = decode_jit(params, tok, cache, jnp.asarray(pos + i, jnp.int32))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0
    toks_s = batch * gen / max(t_decode, 1e-9)
    log.info(
        "prefill %.3fs; decode %d x %d tokens in %.3fs (%.1f tok/s)",
        t_prefill, batch, gen, t_decode, toks_s,
    )
    return np.stack(out_tokens, axis=1), {"prefill_s": t_prefill, "decode_s": t_decode}


def serve_subspace(
    *,
    d: int = 256,
    r: int = 8,
    steps: int = 16,
    rows_per_step: int = 128,
    cadence: int = 4,
    batch: int = 256,
    queries: int = 4096,
    delta: float = 0.2,
    mesh=None,
    topology: str | None = None,
    comm_bits=None,
    plan=None,
    seed: int = 0,
):
    """Serve the streaming eigenspace estimate: ingest, refresh, project.

    A synthetic spiked-covariance stream (``repro.data.synthetic``) feeds
    every shard ``rows_per_step`` rows per step; the service refreshes on
    the cadence; then ``queries`` query rows are projected through the
    served basis in ``batch``-row waves and the projection throughput is
    reported next to the refresh stats.  Each refresh solves locally by
    subspace iteration, the distributed-PCA CLI's solver too (a dense
    eigh at d=8192 takes many minutes to compile for a TPU).
    """
    from repro.comm.topology import DATA_AXIS
    from repro.data import synthetic as syn
    from repro.launch.mesh import make_aggregation_mesh
    from repro.stream import SubspaceService

    mesh = mesh or make_aggregation_mesh()
    m = mesh.shape[DATA_AXIS] * mesh.shape.get("pod", 1)
    svc = SubspaceService(
        mesh, d, r, cadence=cadence, solver="subspace", topology=topology,
        comm_bits=comm_bits, plan=plan,
    )
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    tau = syn.spectrum_m1(d, r, delta=delta)
    _, _, factor = syn.covariance_from_spectrum(k1, tau)
    rows = syn.sample_gaussian(k2, factor, m * steps * rows_per_step)
    stream = rows.reshape(steps, m, rows_per_step, d)

    t0 = time.perf_counter()
    for t in range(steps):
        svc.observe(stream[t])
    jax.block_until_ready(svc.basis)
    t_ingest = time.perf_counter() - t0

    qs = syn.sample_gaussian(k3, factor, queries)
    out = None
    t0 = time.perf_counter()
    for lo in range(0, queries, batch):
        out = svc.project(qs[lo:lo + batch])
    jax.block_until_ready(out)
    t_query = time.perf_counter() - t0
    qps = queries / max(t_query, 1e-9)
    stats = dict(svc.stats)
    stats.update({
        "ingest_s": t_ingest,
        "query_s": t_query,
        "queries_per_s": qps,
    })
    log.info(
        "subspace serve: %d steps ingested in %.3fs (%d refreshes); "
        "%d queries in %.3fs (%.0f q/s, staleness=%d)",
        steps, t_ingest, stats["refreshes"], queries, t_query, qps,
        stats["staleness"],
    )
    return svc, stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--subspace", action="store_true",
                    help="serve the streaming eigenspace estimate "
                         "(repro.stream.SubspaceService) instead of an LM: "
                         "synthetic stream in, cadence refreshes, batched "
                         "query projection throughput out")
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--r", type=int, default=8)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--rows-per-step", type=int, default=128)
    ap.add_argument("--cadence", type=int, default=4)
    ap.add_argument("--queries", type=int, default=4096)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    use_compile_cache()
    if args.subspace:
        _, stats = serve_subspace(
            d=args.d, r=args.r, steps=args.steps,
            rows_per_step=args.rows_per_step, cadence=args.cadence,
            batch=max(args.batch, 64), queries=args.queries,
        )
        for k, v in stats.items():
            print(f"{k}: {v}")
        return
    if not args.arch:
        ap.error("--arch is required (or pass --subspace)")
    toks, stats = serve(
        args.arch,
        batch=args.batch,
        prompt_len=args.prompt_len,
        gen=args.gen,
        reduced=not args.full_config,
    )
    print("generated token matrix:", toks.shape)
    print(stats)


if __name__ == "__main__":
    main()
