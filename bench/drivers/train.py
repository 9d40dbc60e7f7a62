"""Training cells: NoLoCo through the shard_map runtime
(``DistributedTrainer`` wrapped as ``DistributedProgram``, the program of
``launch/train_distributed.py``), one replica per chip.

Set-up builds the one program object, loads the benchmark's own weights
into it, and drives it through three inner steps and one outer step by the
same calls the window makes (``inner_step``, ``float(loss)``,
``maybe_outer_step``: the step body of ``train/loop.py::TrainLoop``).  Those
steps are read for the check against the plain float32 reference.  The
window then runs the same object for ``--seconds``; it ends after
``block_until_ready`` on the parameters.  Once the window has closed and the
device memory peak has been read, the program's state is freed and the
reference replays the checked steps, replica ``r`` on chip ``r``.
"""

from __future__ import annotations

import functools
import gc
import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench.core import harness as H
from bench.core import weights as W
from bench.ref import dense_lm as ref

CHECKED_STEPS = 3


def model_config(cfgfile: dict):
    from repro.models.config import ModelConfig

    return ModelConfig(**cfgfile["model"])


def ref_dims(cfgfile: dict) -> dict:
    m = cfgfile["model"]
    return {
        "d_model": m["d_model"], "num_heads": m["num_heads"],
        "num_kv_heads": m["num_kv_heads"],
        "head_dim": m.get("head_dim") or m["d_model"] // m["num_heads"],
        "d_ff": m["d_ff"], "vocab_size": m["vocab_size"],
        "num_layers": m["num_layers"], "dtype": m.get("dtype", "bfloat16"),
        "rope_theta": m.get("rope_theta", 10000.0),
        "mlp": m["mlp_variant"],
    }


def pairing_seed(seed: int) -> int:
    return seed % 2_147_483_647


class Setup:
    """The program object and its state, built and warmed from the seed."""

    def __init__(self, cell: dict, cfgfile: dict, traffic: dict, seed: int, devs):
        from repro.comm import CommConfig
        from repro.core.outer import OuterConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.train_distributed import DistributedTrainer
        from repro.optim import AdamWConfig
        from repro.parallel import plans as plans_lib
        from repro.train import DistributedProgram

        self.cell, self.traffic, self.seed = cell, traffic, seed
        self.cfg = model_config(cfgfile)
        self.dims = ref_dims(cfgfile)
        self.R = cell["replicas"]
        self.B = traffic["per_replica_batch"]
        self.S = traffic["seq"]
        self.m = cell["outer"]["inner_steps"]
        mesh = make_mesh((self.R, 1), ("data", "model"))
        plan = plans_lib.make_plan("gossip_dp", mesh, shape_kind="train")
        o = cell["outer"]
        self.trainer = DistributedTrainer(
            cfg=self.cfg, mesh=mesh, plan=plan,
            outer_cfg=OuterConfig(method="noloco", inner_steps=self.m,
                                  alpha=o["alpha"], beta=o["beta"]),
            inner_cfg=AdamWConfig(**cell["inner_opt"]),
            comm_cfg=CommConfig(codec=cell["codec"]),
            pairing_pool=cell["pairing_pool"], seed=pairing_seed(seed),
        )
        self.program = DistributedProgram(self.trainer)
        self.batches = W.token_batches(
            seed, traffic["batches"], self.R * self.B, self.S, self.dims["vocab_size"]
        )
        self.state = self.program.init_state(self.batch(0))
        self._load_weights()

    def batch(self, k: int) -> dict:
        t = self.batches[k % len(self.batches)].reshape(self.R, self.B, self.S + 1)
        return {"tokens": t[..., :-1], "labels": t[..., 1:]}

    def _load_weights(self):
        st = self.state
        one = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), st["theta"])
        shard = self.trainer.bundle.theta_shardings
        R, dims = self.R, self.dims
        gen = jax.jit(
            lambda w: jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), W.make_tree(w, one, dims)
            ),
            out_shardings=shard,
        )
        self.words = W.seed_words(self.seed)
        self._gen = gen
        st["theta"] = None
        st["theta"] = gen(self.words)
        st["phi"] = None
        st["phi"] = gen(self.words)

    # -- the calls the window makes ------------------------------------------

    def inner(self, k: int) -> np.ndarray:
        self.state, met = self.program.inner_step(self.state, self.batch(k), None)
        return np.asarray(met["loss"], np.float64)

    def outer(self) -> bool:
        self.state, synced = self.program.maybe_outer_step(self.state)
        return synced


def _leaf_norms(tree):
    """Per leaf, per replica: the norm over everything but the replica axis."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        W.path_name(p): jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                         axis=tuple(range(1, x.ndim))))
        for p, x in flat
    }


_norms = jax.jit(_leaf_norms)
_diff_norms = jax.jit(lambda a, b: _leaf_norms(
    jax.tree.map(lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))


def setup_and_check_steps(su: Setup) -> dict:
    """Drive the three checked inner steps and the outer step; warm every
    program the window reaches.  Returns the program's readings."""
    b1 = su.cell["inner_opt"]["b1"]
    rd: dict = {"loss": []}
    rd["loss"].append(su.inner(0))
    rd["grad"] = {k: v / (1.0 - b1) for k, v in jax.device_get(_norms(su.state["opt"].mu)).items()}
    for k in range(1, CHECKED_STEPS):
        rd["loss"].append(su.inner(k))
    rd["change"] = jax.device_get(_diff_norms(su.state["theta"], su.state["phi"]))
    # the outer step of round 0, through the window's own call: the host
    # counter says a round has just ended
    su.state["inner_step"] = su.m
    if not su.outer():
        raise RuntimeError("the outer step of round 0 did not fire")
    theta0 = su._gen(su.words)
    rd["outer"] = jax.device_get(_diff_norms(su.state["phi"], theta0))
    del theta0
    # the other pairing slots the window can reach: their programs compile
    # and run here, not inside the window
    for slot in range(1, su.cell["pairing_pool"]):
        su.state["inner_step"] = (slot + 1) * su.m
        su.outer()
    su.state["inner_step"] = 0
    jax.block_until_ready(su.state["theta"])
    rd["loss"] = np.stack(rd["loss"])  # (steps, R)
    return rd


def window(su: Setup, seconds: float, first_batch: int):
    """Run the program for ``seconds``; returns counts and times."""
    from jax.profiler import TraceAnnotation

    steps = bad = 0
    t0 = time.perf_counter()
    while True:
        with TraceAnnotation("bench.inner_step"):
            loss = float(jnp.mean(jnp.asarray(su.inner(first_batch + steps))))
        due = su.state["inner_step"] % su.m == 0
        with TraceAnnotation("bench.outer_sync" if due else "bench.outer_check"):
            su.outer()
        steps += 1
        bad += not math.isfinite(loss)
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(su.state["theta"])
    t1 = time.perf_counter()
    return {"steps": steps, "failed": bad, "t0": t0, "t1": t1,
            "tokens": steps * su.R * su.B * su.S}


# ---------------------------------------------------------------------------
# the plain reference of the checked steps
# ---------------------------------------------------------------------------


def reference(cell: dict, dims: dict, words, batches: np.ndarray, R: int, B: int,
              seed: int, devs, precision: str = "f32", half_batch: bool = False,
              exchange: bool = True) -> dict:
    """Float32 replay of the checked steps, replica ``r`` on ``devs[r]``.
    ``half_batch`` and ``exchange=False`` plant the faults the check must
    catch; ``precision="fp8"`` is the control."""
    names = list(ref.param_shapes(dims))
    make, step, diff_norms, delta_of = _ref_programs(
        tuple(sorted(dims.items())), precision, tuple(sorted(cell["inner_opt"].items())))
    out = {"loss": np.zeros((CHECKED_STEPS, R)), "grad": {}, "change": {}, "outer": {}}
    runs = []
    for r in range(R):
        with jax.default_device(devs[r]):
            p = make(words)
            mu = jax.tree.map(jnp.zeros_like, p)
            nu = jax.tree.map(jnp.zeros_like, p)
            runs.append([p, mu, nu, jnp.zeros((), jnp.float32), [], None])
    for t in range(CHECKED_STEPS):
        rows = batches[t]
        for r in range(R):
            p, mu, nu, count, losses, _ = runs[r]
            x = rows[r * B:(r + 1) * B]
            if half_batch:
                x = x[: max(1, B // 2)] if B > 1 else x[:, : x.shape[1] // 2 + 1]
            tok = jax.device_put(jnp.asarray(x[:, :-1]), devs[r])
            lab = jax.device_put(jnp.asarray(x[:, 1:]), devs[r])
            loss, p, mu, nu, count, gn = step(p, mu, nu, count, tok, lab)
            losses.append(loss)
            runs[r][:4] = [p, mu, nu, count]
            if t == 0:
                runs[r][5] = gn
    deltas = []
    for r in range(R):
        p, mu, nu, count, losses, gn = runs[r]
        out["loss"][:, r] = [float(x) for x in losses]
        for k in names:
            out["grad"].setdefault(k, np.zeros(R))[r] = float(gn[k])
        ch = jax.device_get(diff_norms(p, jax.device_put(words, devs[r])))
        for k in names:
            out["change"].setdefault(k, np.zeros(R))[r] = float(ch[k])
        deltas.append(delta_of(p, jax.device_put(words, devs[r])))
        runs[r] = None
    partner = ref.partner_table(0, R, pairing_seed(seed))
    outer = _outer_change(tuple(sorted(dims.items())), tuple(sorted(cell["outer"].items())))
    for r in range(R):
        q = int(partner[r]) if exchange else r
        other = jax.device_put(deltas[q], devs[r])
        norms_r = outer(deltas[r], other, jax.device_put(words, devs[r]))
        for k in names:
            out["outer"].setdefault(k, np.zeros(R))[r] = float(norms_r[k])
        del other
    del deltas
    out["partner"] = partner.tolist()
    return out


@functools.lru_cache(maxsize=None)
def _ref_programs(dims_items: tuple, precision: str, opt_items: tuple):
    """The reference's jitted pieces, built once per (model, precision,
    optimizer) so that several replays in one process compile once."""
    dims, opt = dict(dims_items), dict(opt_items)
    make = jax.jit(lambda w: ref.make_params(w, dims))

    def step_impl(p, mu, nu, count, tok, lab):
        loss, g = jax.value_and_grad(ref.batch_loss)(p, tok, lab, dims, precision)
        p, mu, nu, count, g = ref.adamw_step(p, mu, nu, count, g, opt)
        return loss, p, mu, nu, count, {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in g.items()}

    step = jax.jit(step_impl, donate_argnums=(0, 1, 2))
    diff_norms = jax.jit(lambda p, w: {k: jnp.sqrt(jnp.sum(jnp.square(p[k] - v)))
                                       for k, v in ref.make_params(w, dims).items()})
    delta_of = jax.jit(lambda p, w: {k: p[k] - v for k, v in ref.make_params(w, dims).items()})
    return make, step, diff_norms, delta_of


@functools.lru_cache(maxsize=None)
def _outer_change(dims_items: tuple, outer_items: tuple):
    """Per leaf, the norm of phi' - theta0 after the first outer step: every
    replica starts from the same phi = theta0 with zero outer momentum, and
    theta = theta0 + Delta after the inner steps."""
    dims, o = dict(dims_items), dict(outer_items)
    params = dict(o, gamma=_gamma(o["alpha"]))

    def change(own, other, words):
        phi0 = ref.make_params(words, dims)
        zero = {k: jnp.zeros_like(v) for k, v in phi0.items()}
        theta = {k: phi0[k] + own[k] for k in phi0}
        new_phi, _ = ref.noloco_outer(phi0, zero, theta, other, phi0, params)
        return {k: jnp.sqrt(jnp.sum(jnp.square(new_phi[k] - phi0[k]))) for k in phi0}

    return jax.jit(change)


def _gamma(alpha: float, n: int = 2) -> float:
    """Midpoint of the NoLoCo stability band for gamma (Eq. 74)."""
    s = math.sqrt(n / (2.0 * (n - 1)))
    return 0.5 * (s * alpha + s * math.sqrt(2.0 + alpha * alpha))


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------


def compare(prog: dict, refr: dict) -> dict:
    """Loss: the largest absolute gap over steps and replicas.  Gradient,
    change and outer change: per leaf, the gap between the program's norm and
    the reference's, over the larger of the reference's norm of that leaf and
    of the median leaf; the worst leaf and replica.  Leaves whose reference
    gradient is under a thousandth of the median leaf's are left out."""
    out = {"loss_gap": float(np.max(np.abs(np.asarray(prog["loss"]) - refr["loss"])))}
    gref = {k: np.asarray(v) for k, v in refr["grad"].items()}
    med_g = np.median(np.stack(list(gref.values())), axis=0)
    keep = [k for k, v in gref.items() if np.all(v >= 1e-3 * med_g)]
    out["left_out"] = sorted(set(gref) - set(keep))
    for key, name in (("grad", "grad_gap"), ("change", "change_gap"), ("outer", "outer_gap")):
        rv = {k: np.asarray(refr[key][k]) for k in keep}
        pv = {k: np.asarray(prog[key][k], np.float64).reshape(-1) for k in keep}
        med = np.median(np.stack(list(rv.values())), axis=0)
        worst, where = 0.0, None
        for k in keep:
            g = np.abs(pv[k] - rv[k]) / np.maximum(np.maximum(rv[k], med), 1e-30)
            if float(np.max(g)) >= worst:
                worst, where = float(np.max(g)), k
        out[name] = worst
        out[name + "_leaf"] = where
    return out


def run(cell: dict, cfgfile: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, t_start: float, devs, trace_dir: str):
    su = Setup(cell, cfgfile, traffic, seed, devs)
    prog = setup_and_check_steps(su)
    setup_s = time.time() - t_start
    if trace:
        jax.profiler.start_trace(trace_dir)
    w = window(su, seconds, CHECKED_STEPS)
    if trace:
        jax.profiler.stop_trace()
    dev = H.device_record(devs)
    words, batches, R, B, dims = su.words, su.batches, su.R, su.B, su.dims
    del su
    gc.collect()
    refr = reference(cell, dims, words, batches, R, B, seed, devs)
    cmp = compare(prog, refr)
    wall = w["t1"] - w["t0"]
    return {
        "setup_s": setup_s, "window_s": wall, "attempted": w["steps"], "failed": w["failed"],
        "tokens": w["tokens"], "train_tokens_per_s": w["tokens"] / wall, "device": dev,
        "compare": cmp, "chips": len(devs), "dims": dims, "traffic": traffic,
    }
