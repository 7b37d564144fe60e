"""Entry ``edge``: the edge half of a split CNN, batch after batch.

The window drives one compiled program per batch, as an edge server
would run it on the codes a batch of UEs uploaded: the Pallas
``ops.dequantize`` kernel, the autoencoder's ``compressor.decode``, then
``cnn.forward_from`` from the module after the split point to the
logits. The loop is closed and keeps ``IN_FLIGHT`` batches dispatched, as
an edge server overlaps the host's dispatch of the next batch with the
device's run of the current one: a batch is dispatched once the one
``IN_FLIGHT`` before it has come back.

Set-up draws everything from the seed on the device, with the reference's
own code: the weights, a pool of random images, their UE half, a PCA
autoencoder at the configuration's ratio for the split point, and the
8-bit codes of each pool batch with its (min, max). The program gets the
same weights; nothing it makes is handed to the reference.

After the window, the outputs of a sample of batches drawn from the seed
are compared with the reference, computed as the configuration states
(float32 at the TPU's default precision):

* ``dequant_gap_steps``: the largest gap between the kernel's values and
  Eq. 2's, in code steps of that batch;
* ``logits_gap``: over the sampled images, the largest distance between
  an image's logits and the reference's, as a share of the length of the
  reference's (both as vectors over the classes). It covers the decoder
  and every edge module, and one answer altered shows in full; taken per
  image, it is steadier than the largest single logit's gap, which
  rounding flips amplified through the net move from seed to seed.
"""
from __future__ import annotations

import collections
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.reference import codec
from bench.reference import layers as L

WINDOW_SPAN = "edge_window"
SPAN_NAMES = (WINDOW_SPAN, "edge_call", "edge_wait")
IN_FLIGHT = 2       # batches dispatched and not yet waited for
POOL_BATCHES = 8    # distinct code batches made at set-up, run in turn
CHECK_BATCHES = 6   # batches of the window compared with the reference


def seed_key(seed):
    """A PRNG key that takes every bit of a seed of up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def split_static(tree):
    """(array leaves, (treedef, leaves with None where an array was)):
    the program's parameter trees carry Python tags beside the arrays, so
    only the arrays cross a jit boundary."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    is_arr = [hasattr(x, "shape") for x in leaves]
    arrays = [x for x, a in zip(leaves, is_arr) if a]
    static = [None if a else x for x, a in zip(leaves, is_arr)]
    return arrays, (treedef, static)


def merge_static(arrays, skeleton):
    treedef, static = skeleton
    it = iter(arrays)
    return jax.tree_util.tree_unflatten(
        treedef, [next(it) if s is None else s for s in static])


CONTROL = L.Arith(jnp.bfloat16)


def reference_arith(config):
    """The configuration's stated arithmetic: its dtype, and its
    precision for convolutions and matrix products."""
    return L.Arith(jnp.dtype(config["dtype"]),
                   L.PRECISIONS[config["matmul_precision"]])


def reference_module(cell):
    return harness.load_module(os.path.join(
        cell.bench_dir, "reference", cell.config["reference"] + ".py"))


class Edge:
    """Set-up on construction; then ``window``, ``release``, ``check``."""

    def __init__(self, cell, seed):
        cfg, tr = cell.config, cell.traffic
        self.ref = reference_module(cell)
        self.cfg = cfg
        self.limits = cell.limits
        self.bits = int(cfg["quant_bits"])
        self.batch = B = int(tr["batch"])
        self.pool = P = POOL_BATCHES
        point = int(tr["split"])
        self.split_module = k = int(cfg["split_after"][point - 1])
        self.start = k + 1
        ratio = int(cfg["ae_ratio"][point - 1])
        size = int(cfg["input_size"])

        skeleton = {}
        ref = self.ref

        def make_inputs(key):
            kp, kx = jax.random.split(key)
            params = ref.init(kp, cfg)
            keys = jax.random.split(kx, P)

            def ue_half(k_img):
                imgs = jax.random.normal(k_img, (B, 3, size, size),
                                         jnp.float32)
                return ref.forward(params, imgs, 0, k + 1)

            # the autoencoder is fitted on the first batch; batches then
            # go through the UE half one at a time, which bounds set-up's
            # memory to one batch's activations
            feats = ue_half(keys[0])
            ae = codec.pca_autoencoder(feats, max(1, feats.shape[1] // ratio))
            codes, mn, mx = jax.lax.map(lambda k_img: codec.quantize(
                codec.encode(ae["enc"], ue_half(k_img)), self.bits), keys)
            arrays, skeleton["params"] = split_static(params)
            return arrays, ae["dec"], codes, mn, mx

        arrays, dec, codes, mn, mx = jax.jit(make_inputs)(seed_key(seed))
        self.arrays, self.dec = arrays, dec
        self.skeleton = skeleton["params"]
        self.codes = [codes[i] for i in range(P)]
        self.mn = [mn[i] for i in range(P)]
        self.mx = [mx[i] for i in range(P)]
        del codes, mn, mx
        self.code_shape = tuple(self.codes[0].shape)

        self.step = self._program_fn()
        rng = np.random.default_rng(seed)
        self.order = rng.permutation(P)
        # warm-up: the one shape the window uses, until two calls agree
        t_call = []
        for i in range(3):
            b = self.order[i % P]
            t0 = time.perf_counter()
            jax.block_until_ready(self.step(self.arrays, self.dec,
                                            self.codes[b], self.mn[b],
                                            self.mx[b]))
            t_call.append(time.perf_counter() - t0)
        self.t_step = min(t_call[1:])
        self._rng = rng
        self.kept = []
        self.steps = 0

    def _program_fn(self):
        """The program's edge half for one batch, jitted: the system under
        test."""
        from repro.core import cnn, compressor
        from repro.kernels import ops

        model = cnn.CNN_FACTORY[self.cfg["arch"]](
            int(self.cfg["num_classes"]))
        start, bits, skel = self.start, self.bits, self.skeleton

        def edge_step(arrays, dec, codes, mn, mx):
            params = merge_static(arrays, skel)
            z = ops.dequantize(codes, mn, mx, bits=bits)
            feat = compressor.decode({"dec": dec}, z)
            return z, cnn.forward_from(model, params, feat, start)

        return jax.jit(edge_step)

    def window(self, seconds, spans):
        """Dispatch batches for ``seconds``, ``IN_FLIGHT`` at a time, and
        wait for the last; keep the outputs of ``CHECK_BATCHES`` batches
        drawn from the seed. The rate counts every batch over the time
        until the last has come back."""
        est = max(int(0.9 * seconds / max(self.t_step, 1e-6)), 1)
        keep = set(self._rng.choice(est, size=min(CHECK_BATCHES, est),
                                    replace=False).tolist())
        step, arrays, dec = self.step, self.arrays, self.dec
        codes, mn, mx, order, P = self.codes, self.mn, self.mx, \
            self.order, self.pool
        kept, pending, n = [], collections.deque(), 0

        def wait_oldest():
            i, b, out = pending.popleft()
            with spans.span("edge_wait"):
                jax.block_until_ready(out)
            if i in keep:
                kept.append((b, out))
            return b, out

        with spans.span(WINDOW_SPAN):
            t0 = time.perf_counter()
            t_end = t0 + seconds
            while time.perf_counter() < t_end:
                b = order[n % P]
                with spans.span("edge_call"):
                    out = step(arrays, dec, codes[b], mn[b], mx[b])
                pending.append((n, b, out))
                n += 1
                if len(pending) >= IN_FLIGHT:
                    last = wait_oldest()
            while pending:
                last = wait_oldest()
            elapsed = time.perf_counter() - t0
        self.kept, self.steps = kept or [last], n
        return {"edge_images_per_s": n * self.batch / elapsed}

    def attempted(self):
        return self.steps * self.batch

    def info(self):
        """What the per-layer readers need of this cell."""
        return {"window_span": WINDOW_SPAN, "batches": self.steps,
                "batch": self.batch,
                "split_module": self.split_module,
                "code_shape": self.code_shape, "bits": self.bits}

    def release(self):
        """Free the program's compiled step; keep inputs and the sample."""
        self.step = None

    # ---------------------------------------------------------- the check
    def _reference_fn(self, ar):
        ref, start, bits, skel = self.ref, self.start, self.bits, \
            self.skeleton

        def fn(arrays, dec, codes, mn, mx):
            params = merge_static(arrays, skel)
            z = codec.dequantize(codes, mn, mx, bits, ar.dtype)
            return z, ref.forward(params, codec.decode(dec, z, ar), start,
                                  ar=ar)

        return jax.jit(fn)

    def readings(self):
        """The compared numbers over the sampled batches: the program's
        outputs against the reference computed as the configuration
        states."""
        want_fn = self._reference_fn(reference_arith(self.cfg))
        levels = (1 << self.bits) - 1
        deq, lg = 0.0, 0.0
        for b, out in self.kept:
            args = (self.arrays, self.dec, self.codes[b], self.mn[b],
                    self.mx[b])
            z, lgt = (np.asarray(x, np.float64) for x in jax.device_get(out))
            z_ref, l_ref = (np.asarray(x, np.float64)
                            for x in jax.device_get(want_fn(*args)))
            step = (float(self.mx[b]) - float(self.mn[b])) / levels
            deq = max(deq, float(np.max(np.abs(z - z_ref))) / step)
            lg = max(lg, float(np.max(np.linalg.norm(lgt - l_ref, axis=1)
                                      / np.linalg.norm(l_ref, axis=1))))
        return {"dequant_gap_steps": deq, "logits_gap": lg}

    def check(self):
        """{name: {"value", "limit"}} for the sampled outputs of the
        window, and the images of the sampled batches that failed."""
        readings = self.readings()
        checks = {k: {"value": v, "limit": float(self.limits[k])}
                  for k, v in readings.items()}
        bad = any(not (c["value"] <= c["limit"]) for c in checks.values())
        return checks, (len(self.kept) * self.batch if bad else 0)


def build(cell, seed):
    return Edge(cell, seed)
