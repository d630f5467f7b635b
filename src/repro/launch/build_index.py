"""SSH index-build launcher (the paper's preprocessing stage, Alg. 1).

    PYTHONPATH=src python -m repro.launch.build_index \
        --dataset ecg --points 50000 --length 256 --out /tmp/ssh_db

Sharded, checkpointed, restartable: the stream is hashed in fixed-size
batches; each batch checkpoint is atomic, so a crashed build resumes at
the last completed batch (node-failure tolerance for the 20M-series run).
The finished index is published as a ``repro.db`` database directory —
``TimeSeriesDB.load(out)`` (or ``serve.py --db-dir out``) then answers
queries without ever paying the O(N) signature build again, which is the
operational payoff of the paper's retraining-free hashing.

The build is spec-driven: ``--encoder`` names any registered
``repro.encoders`` encoder (default: the arch registry's ``"ssh"`` spec,
hyper-parameters from ``ssh-ecg`` / ``ssh-randomwalk``), the persisted
``IndexSpec`` travels with the database, and ``--backend`` routes the
signature build through the Pallas ``sketch_conv`` kernel or the jnp
reference.
"""
from __future__ import annotations

import argparse
import shutil
import time

import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_arch
from repro.core.index import SSHIndex
from repro.data.timeseries import extract_subsequences, random_walk, \
    synthetic_ecg
from repro.db import TimeSeriesDB
from repro.encoders import IndexSpec, make_encoder
from repro.launch.compile_cache import enable_compile_cache

_GENERATORS = {"ecg": synthetic_ecg, "randomwalk": random_walk}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", choices=["ecg", "randomwalk"],
                    default="ecg")
    ap.add_argument("--points", type=int, default=50_000)
    ap.add_argument("--length", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--out", type=str, default="/tmp/ssh_db")
    ap.add_argument("--encoder", type=str, default=None,
                    help="registered encoder name (default: the arch's "
                         "'ssh' spec; 'srp'/'ssh-multires' use their "
                         "documented defaults)")
    ap.add_argument("--backend", choices=["auto", "pallas", "jnp"],
                    default="auto",
                    help="signature-build kernel backend (Pallas "
                         "sketch_conv vs jnp reference)")
    args = ap.parse_args()
    enable_compile_cache()

    stream = _GENERATORS[args.dataset](args.points, seed=3)
    series = extract_subsequences(stream, args.length, stride=1, znorm=True)
    n = series.shape[0]

    arch = get_arch(f"ssh-{args.dataset}")
    if args.encoder in (None, "ssh"):
        spec = arch.index_spec()
    else:
        spec = IndexSpec(encoder=args.encoder)
    enc = make_encoder(spec, length=args.length)
    # pin the resolved backend (as SSHIndex.build does) so the persisted
    # database queries with the kernel it was built with on any host
    from repro.kernels import ops
    args.backend = ops.backend_name(ops.resolve_backend(args.backend))

    # batch-checkpointed signature build (scratch space; the published
    # database below is what readers load)
    ck = Checkpointer(f"{args.out}.build_ckpt", keep=2)
    latest, restored = ck.restore_latest(
        {"sigs": jnp.zeros((n, enc.num_hashes), jnp.int32),
         "done": jnp.zeros((), jnp.int32)})
    sigs = np.asarray(restored["sigs"]).copy()
    done = int(restored["done"]) if latest is not None else 0
    if done:
        print(f"resuming at series {done}/{n}")

    t0 = time.time()
    for lo in range(done, n, args.batch):
        hi = min(lo + args.batch, n)
        out = enc.encode_batch(jnp.asarray(series[lo:hi]),
                               backend=args.backend)
        sigs[lo:hi] = np.asarray(out)
        ck.save(hi, {"sigs": jnp.asarray(sigs),
                     "done": jnp.asarray(hi, jnp.int32)})
        rate = (hi - done) / max(time.time() - t0, 1e-9)
        print(f"hashed {hi}/{n} ({rate:.0f} series/s)", flush=True)

    # (TimeSeriesDB clamps knobs the encoder cannot honour, e.g.
    # multiprobe for "srp")
    config = arch.search_config(length=args.length)
    index = SSHIndex(fns=(enc.legacy_functions()
                          if hasattr(enc, "legacy_functions") else None),
                     signatures=jnp.asarray(sigs),
                     keys=enc.band_keys(jnp.asarray(sigs)),
                     series=jnp.asarray(series), encoder=enc,
                     build_backend=args.backend)
    if config.use_lb_cascade and config.band is not None:
        index.candidate_envelopes(config.band)   # persisted with the index
    db = TimeSeriesDB(index, config)
    db.save(args.out)
    # database published durably — the batch-restart scratch (a full
    # (N, K) signature copy per retained checkpoint) is now waste
    shutil.rmtree(f"{args.out}.build_ckpt", ignore_errors=True)
    print(f"index built: {n} series, encoder {spec.encoder!r}, "
          f"{enc.num_hashes} hashes, {enc.num_tables} tables in "
          f"{time.time() - t0:.1f}s; database saved to {args.out} "
          f"(TimeSeriesDB.load / serve.py --db-dir)")


if __name__ == "__main__":
    main()
