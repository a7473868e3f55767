"""Record a small chip trace that holds the program's own spans.

    python3 bench/tests/record_program_trace.py <out.xplane.pb>

Runs on one TPU chip, at small sizes, inside a ``bench.window`` span:
one ``distributed_pca`` job, then a ``SubspaceService`` fed chunks (a
refresh every other one) while a second thread sends queries, so that the
trace holds every ``repro.*`` span the benchmark's readers use.  Every
program is warmed first, and compiled programs come from a persistent
cache, so that no compiler pass fills the trace.  It writes the trace less
the programs' HLO that the ``/host:metadata`` plane holds (hundreds of KB
that no reader uses).  ``bench/tests/data/program_spans_tpu.xplane.pb``
was made so on a TPU v5 lite; ``bench/tests`` reduce it on the CPU.
"""

import glob
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
D, R, ROWS, CHUNK, CHUNKS, QUERIES = 128, 4, 512, 128, 5, 4


def without_hlo(raw: bytes) -> bytes:
    """The serialized trace less the programs' HLO in ``/host:metadata``."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace.FromString(raw)
    for plane in space.planes:
        if plane.name == "/host:metadata":
            plane.ClearField("event_metadata")
    return space.SerializeToString()


def main(out: str) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import data
    from repro.comm import DATA_AXIS
    from repro.core.distributed import distributed_pca
    from repro.launch.mesh import make_mesh
    from repro.stream import SubspaceService

    jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU; JAX found {dev.platform!r}", file=sys.stderr)
        return 2
    mesh = make_mesh((1,), (DATA_AXIS,), devices=[dev])
    kf, kr, kc, kq = jax.random.split(data.seed_key(5), 4)
    factor = data.sampling_factor(kf, D, R, {"model": "M1", "lam_l": 0.5,
                                             "lam_h": 1.0, "delta": 0.2})
    rows = jax.device_put(data.sample_gaussian(kr, factor, ROWS),
                          NamedSharding(mesh, P(DATA_AXIS, None)))
    chunks = data.sample_gaussian(kc, factor, CHUNKS * CHUNK).reshape(
        CHUNKS, 1, CHUNK, D)
    queries = data.sample_gaussian(kq, factor, QUERIES * 32).reshape(
        QUERIES, 32, D)

    def pca():
        return distributed_pca(rows, mesh, R)

    # Warm every program: the job's (each call builds it anew, and the
    # second build comes from the cache) and the service's, on the one
    # service the window uses.  The local solves are the dense ``eigh``,
    # which puts fewer ops in the trace than subspace iteration.
    for _ in range(2):
        jax.block_until_ready(pca())
    svc = SubspaceService(mesh, D, R, cadence=2)
    # Split on the host now: slicing in the window would compile there.
    chunks, queries = list(chunks), list(queries)
    for c in chunks[:3]:
        svc.observe(c)
    jax.block_until_ready(svc.project(queries[0]))
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as the harness traces
    jax.profiler.start_trace(tmp, profiler_options=opts)
    time.sleep(2.0)  # the tracer's start-up stall

    def ask():
        for q in queries:
            jax.block_until_ready(svc.project(q))
            time.sleep(0.002)

    with jax.profiler.TraceAnnotation("bench.window"):
        jax.block_until_ready(pca())
        asker = threading.Thread(target=ask)
        asker.start()
        for c in chunks[3:]:
            svc.observe(c)
        asker.join()
        jax.block_until_ready(svc.basis)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as f:
        raw = f.read()
    with open(out, "wb") as f:
        f.write(without_hlo(raw))
    print(f"{out}: {os.path.getsize(out)} bytes; stats {svc.stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
