"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

Run on a chip (``python3 benchmark/tests/record_trace.py OUT.xplane.pb.gz``):
under a ``bench.window`` annotation it checks a few short register
histories, so the trace holds bitset kernel launches, host syncs and
idle gaps, and writes the profiler's xplane file gzipped to OUT.
"""

import glob
import gzip
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(out: str) -> None:
    import jax

    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.history.history import History
    from traffic import generate as gen

    assert jax.devices()[0].platform == "tpu", jax.devices()
    cfg = {"ops_per_key": 300, "processes_per_key": 10, "values": 5,
           "crashes_per_key": {"read": 1, "write": 1, "cas": 1}}
    hs = [History(gen.key_history(cfg, gen.rng_for(7, i))[0]) for i in range(3)]
    checker = LinearizableChecker()
    checker.check({}, hs[0])
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for h in hs:
            with jax.profiler.TraceAnnotation("bench.check"):
                checker.check({}, h)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    with open(path, "rb") as f, gzip.open(out, "wb") as g:
        g.write(f.read())
    shutil.rmtree(log_dir)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
