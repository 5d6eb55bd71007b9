"""Build one deployment from its configuration file and the seed.

The benchmark makes the archive and the quantizers (its "weights") on
the device, hands the quantizers to the program through
``StreamingIndex.from_parts``, and loads the archive through the
server's own write path, so every write is in the op log the check
replays.

Every seed gets the same set of archive series in another order: the
series and the quantizers come from the configuration's
``data.dataset_seed``, the load order, the queries, their arrivals and
the writers' series from ``--seed``.  The program sizes its compacted
segment by its longest inverted list, so an archive drawn afresh per
seed would change both the fine stage's work and its compiled shape
with the seed.
"""

from __future__ import annotations

import time

import numpy as np

from . import reference as R
from .check import Geometry
from .loadgen import Op, OpLog


def seed_key(seed: int, *salt: int):
    """A JAX key from a seed of any size (seeds may exceed 32 bits)."""
    import jax

    word = np.random.SeedSequence([seed, *salt]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def host_walks(seed: int, batch: int, n: int, length: int) -> np.ndarray:
    """Insert batch ``batch`` of the writers: fresh z-normalised walks."""
    rng = np.random.default_rng([seed, 7, batch])
    x = np.cumsum(rng.standard_normal((n, length), dtype=np.float32), axis=1)
    mu = x.mean(1, keepdims=True)
    sd = x.std(1, keepdims=True)
    return ((x - mu) / np.maximum(sd, 1e-9)).astype(np.float32)


class Deployment:
    """The served index, its data and the op log of every write."""

    def __init__(self, cfg: dict, seed: int):
        import jax
        import jax.numpy as jnp

        from repro.core.pq import PQCodebook, PQConfig
        from repro.index import IndexConfig, StreamingIndex
        from repro.serve_index import IndexServer, ServeConfig

        self.cfg = cfg
        self.seed = seed
        t0 = time.perf_counter()
        self.timings = {}
        g = self.g = Geometry(cfg)
        d, pq, ivf, q = cfg["data"], cfg["pq"], cfg["ivf"], cfg["quantizers"]
        n_base, n_hot = d["n_series"], d["n_hot"]
        pool = R.random_walks(seed_key(d["dataset_seed"], 1), n=n_base + n_hot, length=g.L)
        rng = np.random.default_rng([seed, 1])
        order = np.concatenate([rng.permutation(n_base), n_base + rng.permutation(n_hot)])
        self.base = np.asarray(pool)[order]
        coarse, cents, upper, lower = R.make_quantizers(
            seed_key(d["dataset_seed"], 2),
            pool[: q["train_sample"]],
            n_lists=g.n_lists, n_sub=g.M, k=g.K, level=g.level, tail=g.tail,
            window=g.w, iters=q["kmeans_iters"],
        )
        jax.block_until_ready(cents)
        self.timings["data_and_quantizers_s"] = time.perf_counter() - t0
        # the symmetric code table the codebook carries; search never reads it
        lut = np.stack([R.dtw_cdist(cents[m], cents[m], g.w) for m in range(g.M)])
        self.quant = {"coarse": coarse, "cents": cents, "upper": upper, "lower": lower}
        icfg = IndexConfig(
            PQConfig(
                n_sub=g.M, codebook_size=g.K, window_frac=pq["window_frac"],
                use_prealign=pq["use_prealign"], wavelet_level=g.level,
                tail_frac=pq["tail_frac"], refine_frac=pq["refine_frac"],
            ),
            n_lists=g.n_lists, hot_capacity=ivf["hot_capacity"],
            coarse_window_frac=ivf["coarse_window_frac"],
        )
        # the program's own geometry has to be the one the reference assumes
        have = (icfg.pq.tail(g.L), icfg.pq.subseq_len(g.L), icfg.pq.window(g.L),
                icfg.coarse_window(g.L), icfg.pq.refine_t())
        want = (g.tail, g.S, g.w, g.wc, g.T)
        if have != want or not pq["use_prealign"]:
            raise SystemExit(f"geometry mismatch: program {have}, reference {want}")
        index = StreamingIndex.from_parts(
            icfg, coarse, PQCodebook(cents, jnp.asarray(lut), upper, lower), g.L
        )
        s = cfg["serving"]
        self.log = OpLog()
        self.srv = IndexServer(
            index,
            ServeConfig(
                n_probe=s["n_probe"], topk=s["topk"],
                coalesce_window_s=s["coalesce_window_s"], q_buckets=tuple(s["q_buckets"]),
                queue_bound=s["queue_bound"], shed_policy=s["shed_policy"],
                apply_batch=s["apply_batch"],
            ),
            on_publish=self.log.on_publish,
        ).start()
        self.timings["index_object_s"] = time.perf_counter() - t0
        self.inserted = {}  # batch index -> rows of the writers' inserts
        self.n_ids = n_base + n_hot
        batch = d["load_batch"]
        for i in range(0, n_base, batch):
            self.write(Op("insert", ids=np.arange(i, min(i + batch, n_base), dtype=np.int32),
                          rows=self.base[i : i + batch]))
        self.write(Op("compact"))
        if n_hot:
            self.write(Op("insert", ids=np.arange(n_base, n_base + n_hot, dtype=np.int32),
                          rows=self.base[n_base:]))
        jax.block_until_ready(self.srv.view.segments[0].codes)
        self.timings["loaded_s"] = time.perf_counter() - t0

    def write(self, op: Op) -> Op:
        """A set-up write: submitted, then waited for."""
        self.log.submit(self.srv, op)
        if op.shed:
            raise SystemExit("set-up write was shed")
        op.future.result()
        return op

    def rows_of(self, ids) -> np.ndarray:
        """Raw series of external ids (base archive or writers' batches)."""
        ids = np.asarray(ids, np.int64)
        out = np.empty((len(ids), self.g.L), np.float32)
        base = ids < len(self.base)
        out[base] = self.base[ids[base]]
        rest = np.flatnonzero(~base)
        if len(rest):
            n = self.batch_rows
            for j in rest.tolist():
                b, r = divmod(int(ids[j]) - len(self.base), n)
                out[j] = self.batch(b)[r]
        return out

    # writers' batches, made from the seed and kept once made
    batch_rows = 0

    def batch(self, b: int) -> np.ndarray:
        if b not in self.inserted:
            self.inserted[b] = host_walks(self.seed, b, self.batch_rows, self.g.L)
        return self.inserted[b]

    def query_pool(self, n: int, noise: float) -> tuple:
        """``n`` queries: archive series plus N(0, noise^2), after the Hydra
        query workloads; returns (queries, source ids)."""
        rng = np.random.default_rng([self.seed, 3])
        src = rng.integers(0, len(self.base), n)
        Q = self.base[src] + noise * rng.standard_normal((n, self.g.L)).astype(np.float32)
        return Q.astype(np.float32), src

    def warm(self, buckets, per_request: int = 1) -> None:
        """Build the programs of each bucket size the traffic reaches: a
        burst of requests of ``per_request`` rows that coalesces into one
        batch of the bucket, so every row offset the coalescer slices the
        answer at is built too."""
        import jax

        for b in buckets:
            t0 = time.perf_counter()
            for _ in range(2):
                futs = [self.srv.submit_search(self.base[i : i + per_request])
                        for i in range(0, b, per_request)]
                for f in futs:
                    r = f.result()
                    jax.block_until_ready((r.dist, r.ids))
            self.timings[f"warm_{b}x{per_request}_s"] = time.perf_counter() - t0

    def warm_merges(self, buckets, counts) -> None:
        """Build the final top-k merge of the search for each number of
        parts in ``counts`` (sealed segments, then the hot buffer when it
        holds rows) at each bucket size.  The merge is jitted per number of
        parts, and a segment count is only reached by writing that many
        flushes, so it is warmed by calling it on arrays of the shapes and
        types the search hands it: ``(bucket, topk)`` distances and ids,
        not committed to a device, the hot buffer's distances weakly typed
        (as the program's hot scan returns them)."""
        import jax
        import jax.numpy as jnp

        from repro.index.streaming import _merge_topk

        t0 = time.perf_counter()
        k = self.g.topk
        for b in buckets:
            d = jnp.zeros((b, k), jnp.float32)
            d_hot = jnp.full((b, k), 0.0)  # weakly typed float32
            i = jnp.zeros((b, k), jnp.int32)
            for n in counts:
                for last in (d, d_hot):
                    jax.block_until_ready(_merge_topk((d,) * (n - 1) + (last,), (i,) * n, topk=k))
        self.timings[f"warm_merges_{min(counts)}-{max(counts)}_s"] = time.perf_counter() - t0
