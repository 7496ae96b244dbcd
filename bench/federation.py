"""Federation cells: ``FederatedRunner.run_round`` on the cell's chips.

Set-up builds the runner from the configuration and the cell's job file
(on more than one chip with the program's federated mesh, which puts the
client stack on its "data" axis), replaces its models with weights the
benchmark draws from the seed (one jitted call, in the served type, placed
as the program placed its own), and drives the first rounds through the
window's own call and feed: they compile the round and give the readings
the reference is compared on.  The client stack the compiled round
returns must lie on every chip of the cell, with no more than its share of
the clients' bytes on each (``stack_chips``, ``clients_per_chip``).  The
window then calls ``run_round(evaluate=False)`` and ``sync()`` until
``--seconds`` have passed; ``round_s`` is the window over the rounds it
completed.

After the window the runner is freed and the plain reference (``Reference``
below, over each model's reference module: the ML-ECS round of client
CCL/AMT steps, MMA aggregation, SE-CCL and redistribution, in float32 at
HIGHEST precision) replays the same first rounds from the same weights and
feed on one chip.  Compared, leaf by leaf, for every client and both server
models:

- ``grad_gap``: Adam's first moment after round 1 (the gradients as the
  optimizer got them);
- ``change_gap``: the change of every trainable leaf after the checked
  rounds.

Each is the worst leaf's gap between the program's norm and the
reference's, over the larger of the reference's norm of that leaf and of
the median leaf.  Leaves whose reference gradient is under a thousandth of
the median leaf's are left out of both.
"""
from __future__ import annotations

import gc
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, model

F32 = jnp.float32
TRACED_ROUNDS = 3          # rounds in the traced slice of a --trace 1 run


# ---------------------------------------------------------------------------
# data and weights

def corpus(seed: int, job: dict, m: dict) -> dict:
    """The synthetic multimodal corpus of the ML-ECS reproduction: modality
    features carry a latent class, the text ends in the class's template,
    and the loss covers the template only."""
    rng = np.random.default_rng(seed)
    n, S, T = job["samples"], job["seq_len"], job["template_len"]
    C, M, F, lat = (job["n_classes"], m["n_modalities"], m["modality_dim"],
                    32)
    vocab = m["vocab_size"]
    mu = rng.normal(size=(C, lat)).astype(np.float32)
    W = (rng.normal(size=(M, lat, F)) / np.sqrt(lat)).astype(np.float32)
    templates = rng.integers(2, vocab, size=(C, T)).astype(np.int32)
    cls = rng.integers(0, C, size=(n,)).astype(np.int32)
    z = mu[cls] + 0.3 * rng.normal(size=(n, lat)).astype(np.float32)
    feats = np.einsum("nl,mld->nmd", z, W).astype(np.float32)
    feats += 0.3 * rng.normal(size=feats.shape).astype(np.float32)
    ctx = rng.integers(2, vocab, size=(n, S - T)).astype(np.int32)
    loss_mask = np.zeros((n, S), np.float32)
    loss_mask[:, S - T:] = 1.0
    return {"tokens": np.concatenate([ctx, templates[cls]], 1),
            "loss_mask": loss_mask, "modality_feats": feats, "label": cls,
            "template_start": np.full((n,), S - T, np.int32),
            "templates": templates}


def _keys(k):
    return {"slm": jax.random.fold_in(k, 0), "srv_slm": jax.random.fold_in(k, 20),
            "llm": jax.random.fold_in(k, 30),
            "client": lambda j: jax.random.fold_in(k, 10 + j)}


def _draw_pieces(key, slm: dict, llm: dict, n: int):
    """The SLM backbone (shared by every client and the server SLM), each
    client's and the server SLM's trainable leaves, the server LLM."""
    ks = _keys(key)
    dt = jnp.dtype(slm["dtype"])
    bb = model._draw(ks["slm"], model.backbone(slm).backbone_shapes(slm), dt,
                     slm["vocab_size"])
    per = [model._draw(ks["client"](j), model.personal_shapes(slm, 0.0), dt)
           for j in range(n)]
    srv = model._draw(ks["srv_slm"], model.personal_shapes(slm, 0.0), dt)
    return bb, per, srv, model.draw_model(ks["llm"], llm)


def draw_pieces(wseed: int, slm: dict, llm: dict, n: int):
    """The weights as the reference takes them, in one jitted call."""
    return jax.jit(lambda k: _draw_pieces(k, slm, llm, n))(
        jax.random.key(wseed))


def draw_program_weights(wseed: int, slm: dict, llm: dict, n: int,
                         placement=None):
    """The same weights in the program's layout, in one jitted call:
    (client stack with a leading client axis, server SLM, server LLM),
    made where ``placement`` (the same tuple of shardings) puts them."""
    def f(key):
        bb, per, srv, big = _draw_pieces(key, slm, llm, n)
        stack = {k: jnp.stack([v] * n) for k, v in bb.items()}
        stack.update({k: jnp.stack([p[k] for p in per]) for k in per[0]})
        return stack, {**bb, **srv}, big
    if placement is None:
        return jax.jit(f)(jax.random.key(wseed))
    return jax.jit(f, out_shardings=placement)(jax.random.key(wseed))


# ---------------------------------------------------------------------------
# the plain reference of one ML-ECS round

def _store(x, dtype):
    """A parameter as the configuration stores it (its ``dtype``)."""
    return x.astype(dtype).astype(F32)


def _adam(opt_cfg: dict, lr: float, dtype):
    """AdamW with global-norm clipping; moments in float32, the parameter
    stored in ``dtype`` after each update, as the configuration states."""
    b1, b2, eps, clip = (opt_cfg["b1"], opt_cfg["b2"], opt_cfg["eps"],
                         opt_cfg["clip_norm"])

    def init(t):
        z = {k: jnp.zeros(v.shape, F32) for k, v in t.items()}
        return {"step": jnp.zeros((), jnp.int32), "mu": z, "nu": dict(z)}

    def update(t, g, st):
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        c = jnp.minimum(1.0, clip / (norm + 1e-9))
        g = {k: v * c for k, v in g.items()}
        step = st["step"] + 1
        s = step.astype(F32)
        mu = {k: b1 * st["mu"][k] + (1 - b1) * g[k] for k in g}
        nu = {k: b2 * st["nu"][k] + (1 - b2) * g[k] ** 2 for k in g}
        new = {k: (t[k].astype(dtype) + (-lr * (mu[k] / (1 - b1 ** s))
                   / (jnp.sqrt(nu[k] / (1 - b2 ** s)) + eps)).astype(dtype)
                   ).astype(F32) for k in t}
        return new, {"step": step, "mu": mu, "nu": nu}
    return init, update


def _pool(x, target, axis):
    n = x.shape[axis]
    if n == target:
        return x
    crop = (n // target) * target
    x = jax.lax.slice_in_dim(x, 0, crop, axis=axis)
    shape = list(x.shape)
    shape[axis:axis + 1] = [target, crop // target]
    return jnp.mean(x.reshape(shape), axis=axis + 1)


def pooled_kl(student, teacher, temperature=2.0):
    """Eq. 14: KL(teacher || student) of temperature-softened logits,
    average-pooled to the shorter sequence and the smaller vocabulary,
    summed over positions and averaged over the batch."""
    S = min(student.shape[1], teacher.shape[1])
    V = min(student.shape[2], teacher.shape[2])
    s = _pool(_pool(student, S, 1), V, 2) / temperature
    t = _pool(_pool(teacher, S, 1), V, 2) / temperature
    lt = jax.nn.log_softmax(t, -1)
    kl = jnp.sum(jnp.exp(lt) * (lt - jax.nn.log_softmax(s, -1)), -1)
    return jnp.mean(jnp.sum(kl, -1))


class Reference:
    """The round of Algorithm 1 in plain jnp: ``prec`` "f32" is the
    reference, "fp8" its lower-precision control."""

    def __init__(self, conf: dict, prec: str = "f32"):
        self.slm, self.llm = conf["clients"]["model"], conf["server_llm"]
        self.proto = conf["protocol"]
        self.dtype = jnp.dtype(self.slm["dtype"])
        init, upd = _adam(conf["optimizer"], self.proto["lr"], self.dtype)
        self.opt_init = init
        slm, llm, proto, prec_ = self.slm, self.llm, self.proto, prec

        def mlecs(train, bb, m, batch, anchor, ccl_weight):
            p = model.nest({**bb, **train})
            soft, mods, fused = model.connector(
                p["connector"], m, batch["modality_feats"],
                batch["modality_mask"], prec_)
            logits = model.backbone(m).forward(p, m, batch["tokens"], soft,
                                               prec_)
            lm = model.lm_ce(logits, batch["tokens"], batch["loss_mask"])
            if ccl_weight:
                anc = fused if anchor is None else anchor
                lm = lm + ccl_weight * model.contrastive(
                    anc, mods, batch["modality_mask"], proto["n_negatives"])
            return lm

        def client_step(train, opt, bb, batch, anchor, ccl):
            w = proto["ccl_weight"] if ccl else 0.0
            g = jax.grad(lambda t: mlecs(t, bb, slm, batch, anchor, w))(train)
            return upd(train, g, opt)

        def anchor_fn(llm_train, batch):
            cp = {k.split("/", 1)[1]: v for k, v in llm_train.items()
                  if k.startswith("connector/")}
            ones = jnp.ones(batch["modality_mask"].shape, bool)
            return model.connector(cp, llm, batch["modality_feats"], ones,
                                   prec_)[2]

        def se_step(lt, st, lo, so, lbb, sbb, batch):
            def total(lt, st):
                l_llm = mlecs(lt, lbb, llm, batch, None, proto["ccl_weight"])
                l_slm = mlecs(st, sbb, slm, batch, None, 0.0)
                y_l = model.backbone(llm).forward(
                    model.nest({**lbb, **lt}), llm, batch["tokens"], None,
                    prec_)
                y_s = model.backbone(slm).forward(
                    model.nest({**sbb, **st}), slm, batch["tokens"], None,
                    prec_)
                kt = proto["kt_weight"]
                return (l_llm + kt * pooled_kl(y_l, jax.lax.stop_gradient(y_s))
                        + l_slm + kt * pooled_kl(y_s,
                                                 jax.lax.stop_gradient(y_l)))
            gl, gs = jax.grad(total, argnums=(0, 1))(lt, st)
            lt, lo = upd(lt, gl, lo)
            st, so = upd(st, gs, so)
            return lt, st, lo, so

        self.client_step = jax.jit(client_step, static_argnames=("ccl",))
        self.anchor_fn = jax.jit(anchor_fn)
        self.se_step = jax.jit(se_step)

    def start(self, pieces) -> dict:
        bb, per, srv, big = pieces
        f32 = lambda d: {k: v.astype(F32) for k, v in d.items()}  # noqa: E731
        lt = f32({k: v for k, v in big.items() if model.is_trainable(k)})
        return {"slm_bb": bb,
                "llm_bb": {k: v for k, v in big.items()
                           if not model.is_trainable(k)},
                "clients": [(f32(p), self.opt_init(f32(p))) for p in per],
                "srv_slm": (f32(srv), self.opt_init(f32(srv))),
                "srv_llm": (lt, self.opt_init(lt))}

    def round(self, s: dict, feed: dict, channel=None) -> dict:
        """One round on the captured feed: client CCL + AMT steps, MMA,
        SE-CCL, redistribution.  ``channel`` (optional) maps the stacked
        uploads to what the server receives and the delivery to what the
        clients receive."""
        n = len(s["clients"])
        lt, lo = s["srv_llm"]
        clients = []
        for j, (t, o) in enumerate(s["clients"]):
            for k in range(feed["pub"]["tokens"].shape[0]):
                b = {x: v[k, j] for x, v in feed["pub"].items()}
                anc = self.anchor_fn(lt, b)
                t, o = self.client_step(t, o, s["slm_bb"], b, anc, ccl=True)
            for k in range(feed["priv"]["tokens"].shape[0]):
                b = {x: v[k, j] for x, v in feed["priv"].items()}
                t, o = self.client_step(t, o, s["slm_bb"], b, None, ccl=False)
            clients.append((t, o))
        counts = np.array([feed["priv"]["modality_mask"][0, j, 0].sum()
                           for j in range(n)], np.float32)
        w = counts / counts.sum()
        ups = [{k: v for k, v in t.items() if model.is_lora(k)}
               for t, _ in clients]
        if channel is not None:
            agg = channel.uplink(ups, w)
        else:
            agg = {k: sum(float(w[j]) * ups[j][k] for j in range(n))
                   for k in ups[0]}
        st, so = s["srv_slm"]
        st = {**st, **{k: _store(v, self.dtype) for k, v in agg.items()}}
        for k in range(feed["server"]["tokens"].shape[0]):
            b = {x: v[k] for x, v in feed["server"].items()}
            lt, st, lo, so = self.se_step(lt, st, lo, so, s["llm_bb"],
                                          s["slm_bb"], b)
        down = {k: v for k, v in st.items() if model.is_lora(k)}
        if channel is not None:
            down = {k: _store(v, self.dtype)
                    for k, v in channel.downlink(down).items()}
        clients = [({**t, **down}, o) for t, o in clients]
        return {**s, "clients": clients, "srv_slm": (st, so),
                "srv_llm": (lt, lo)}


# ---------------------------------------------------------------------------
# readings and their comparison

def _norms(flat: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(F32)))))
            for k, v in flat.items()}


def ref_readings(s0: dict, s1: dict, s3: dict) -> dict:
    """{model: {leaf: (first-moment norm after round 1, change norm)}}."""
    out = {}
    names = [f"client{j}" for j in range(len(s0["clients"]))]
    trip = [(names[j], s0["clients"][j][0], s1["clients"][j][1],
             s3["clients"][j][0]) for j in range(len(names))]
    trip += [("server_slm", s0["srv_slm"][0], s1["srv_slm"][1],
              s3["srv_slm"][0]),
             ("server_llm", s0["srv_llm"][0], s1["srv_llm"][1],
              s3["srv_llm"][0])]
    for name, t0, o1, t3 in trip:
        mu = _norms(o1["mu"])
        ch = _norms({k: t3[k] - t0[k] for k in t0})
        out[name] = {k: (mu[k], ch[k]) for k in t0}
    return out


def compare(prog: dict, ref: dict) -> dict:
    """Worst-leaf relative gaps of the program's readings against the
    reference's, leaving out leaves whose reference gradient is nought to
    rounding (under 1e-3 of the median leaf's)."""
    grads = [g for leaves in ref.values() for g, _ in leaves.values()]
    changes = [c for leaves in ref.values() for _, c in leaves.values()]
    g_med, c_med = float(np.median(grads)), float(np.median(changes))
    worst = {"grad_gap": 0.0, "change_gap": 0.0}
    where = {}
    skipped = 0
    for name, leaves in ref.items():
        for k, (g, c) in leaves.items():
            if g < 1e-3 * g_med:
                skipped += 1
                continue
            pg, pc = prog[name][k]
            for key, a, b, med in (("grad_gap", pg, g, g_med),
                                   ("change_gap", pc, c, c_med)):
                gap = abs(a - b) / max(b, med)
                if gap > worst[key]:
                    worst[key], where[key] = gap, f"{name}:{k}"
    return {**worst, "where": where, "skipped_leaves": skipped}


def program_readings(rt, runner, init: dict, stage: dict):
    """Fill ``stage`` from the runner's state: first-moment norms after
    round 1 (``mu``) or change norms after the checked rounds
    (``change``)."""
    n = rt.n
    if "mu" not in stage:
        mus = {f"client{j}": _norms({k: v[j] for k, v in
                                     rt.stacked_opt["mu"].items()})
               for j in range(n)}
        mus["server_slm"] = _norms(runner.server_slm_opt["mu"])
        mus["server_llm"] = _norms(runner.server_llm_opt["mu"])
        stage["mu"] = mus
        return
    flat = model.flatten(rt.stacked_params)
    ch = {f"client{j}": _norms({k: flat[k][j] - init[f"client{j}"][k]
                                for k in init[f"client{j}"]})
          for j in range(n)}
    for name, tree in (("server_slm", runner.server_slm),
                       ("server_llm", runner.server_llm)):
        f = model.flatten(tree)
        ch[name] = _norms({k: f[k] - init[name][k] for k in init[name]})
    stage["change"] = ch


def _host(tree):
    return jax.tree.map(lambda a: np.array(a), tree)


# ---------------------------------------------------------------------------
# the run

def build_runner(conf: dict, job: dict, seed: int, devs):
    """The program's runner of the cell; on more than one device with the
    program's federated mesh, which must span exactly ``devs``."""
    from bench.serving import program_config
    from repro.core.channel import ChannelSpec
    from repro.core.federated import FederatedRunner
    from repro.core.spec import ClientCohort, FederationSpec
    from repro.launch.mesh import make_federated_mesh

    slm = program_config(conf["clients"]["model"])
    llm = program_config(conf["server_llm"])
    proto = conf["protocol"]
    chan = job.get("channel")
    spec = FederationSpec(
        cohorts=(ClientCohort(model=slm, n_clients=conf["clients"]["n"],
                              name="slm"),),
        server_llm=llm, rounds=1, local_steps_ccl=job["local_steps_ccl"],
        local_steps_amt=job["local_steps_amt"],
        server_steps=job["server_steps"], batch_size=job["batch_size"],
        lr=proto["lr"], rho=proto["rho"], n_negatives=proto["n_negatives"],
        kt_weight=proto["kt_weight"], seed=seed, robust=job["robust"],
        channel=ChannelSpec(**chan) if chan else None)
    data = corpus(model.np_seed(seed, 4), job, conf["clients"]["model"])
    if len(devs) == 1:
        return FederatedRunner(spec, data)
    mesh = make_federated_mesh()
    if set(mesh.devices.flat) != set(devs):
        raise RuntimeError(f"the federated mesh spans {mesh.devices.size} "
                           f"devices, the cell {len(devs)}")
    return FederatedRunner(spec, data, mesh=mesh)


def _models(runner) -> tuple:
    (rt,) = runner.cohorts
    return (("clients", rt.stacked_params), ("server_slm", runner.server_slm),
            ("server_llm", runner.server_llm))


def layout(runner) -> dict:
    """Paths, shapes and dtypes of the runner's three models."""
    return {name: {k: (tuple(v.shape), str(v.dtype))
                   for k, v in model.flatten(tree).items()}
            for name, tree in _models(runner)}


def placement(runner) -> tuple:
    """The shardings of the runner's three models, flat."""
    return tuple({k: v.sharding for k, v in model.flatten(tree).items()}
                 for _, tree in _models(runner))


def stack_spread(runner) -> tuple:
    """(devices that hold a part of the client stack, the most clients'
    worth of its bytes one device holds): parameters and optimizer state,
    each leaf's shard counted on every device that holds one."""
    (rt,) = runner.cohorts
    held, total = {}, 0
    for x in jax.tree.leaves((rt.stacked_params, rt.stacked_opt)):
        shard = math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
        for d in x.sharding.device_set:
            held[d] = held.get(d, 0) + shard
        total += x.nbytes
    return len(held), max(held.values()) / (total / rt.n)


def install(runner, want_all: dict, stack, srv, big) -> None:
    """Replace the runner's models by the benchmark's weights, which must
    have the program's layout."""
    (rt,) = runner.cohorts
    for name, mine in (("clients", stack), ("server_slm", srv),
                       ("server_llm", big)):
        want = want_all[name]
        have = {k: (tuple(v.shape), str(v.dtype)) for k, v in mine.items()}
        if want != have:
            diff = sorted(set(want.items()) ^ set(have.items()))[:6]
            raise RuntimeError(f"{name}: the program's layout differs from "
                               f"the benchmark's: {diff}")
    rt.stacked_params = model.nest(stack)
    runner.server_slm = model.nest(srv)
    runner.server_llm = model.nest(big)
    rt.last_global = {k: jnp.copy(v) for k, v in srv.items()
                      if model.is_lora(k)}


def capture_feed(runner, store: list, upto: int, half: bool = False) -> None:
    """Keep host copies of the first ``upto`` rounds' batches, as the
    runner assembles them for its round.  ``half`` plants a fault: the
    program then trains on half of every batch."""
    inner = runner._assemble_round

    def wrapped():
        pubs, privs, server = inner()
        if len(store) < upto:
            store.append({"pub": _host(pubs[0]), "priv": _host(privs[0]),
                          "server": _host(server)})
        if half:
            return half_batch((pubs, privs, server))
        return pubs, privs, server
    runner._assemble_round = wrapped


def rows_distinct(feed: list) -> bool:
    """Within each client's stream and the server's, the checked rounds
    train on rows that all differ."""
    def rows(batches):
        t = batches["tokens"]
        return t.reshape(-1, t.shape[-1])
    n = feed[0]["priv"]["tokens"].shape[1]
    streams = [np.concatenate([rows({"tokens": f[s]["tokens"][:, j]})
                               for f in feed]) for s in ("pub", "priv")
               for j in range(n)]
    streams.append(np.concatenate([rows(f["server"]) for f in feed]))
    return all(len({r.tobytes() for r in s}) == len(s) for s in streams)


def run(w, conf, job, limits, seed, seconds, trace, devs, t_start,
        faults=(), prec_ctl=None):
    """One run of a federation cell.  Returns (result dict, checks)."""
    counter = common.CompileCounter()
    slm, llm = conf["clients"]["model"], conf["server_llm"]
    n = conf["clients"]["n"]
    wseed = model.np_seed(seed, 1)
    n_check = job["check_rounds"]
    runner = build_runner(conf, job, model.np_seed(seed, 5), devs)
    built_peaks = common.memory_peaks(devs)
    (rt,) = runner.cohorts
    want = layout(runner)
    place = placement(runner) if runner.mesh is not None else None
    rt.stacked_params = runner.server_slm = runner.server_llm = None
    gc.collect()
    stack, srv, big = draw_program_weights(wseed, slm, llm, n, place)
    init = {f"client{j}": {k: np.array(v[j]) for k, v in stack.items()
                           if model.is_trainable(k)} for j in range(n)}
    init["server_slm"] = {k: np.array(v) for k, v in srv.items()
                          if model.is_trainable(k)}
    init["server_llm"] = {k: np.array(v) for k, v in big.items()
                          if model.is_trainable(k)}
    install(runner, want, stack, srv, big)
    del stack, srv, big
    feed: list = []
    capture_feed(runner, feed, n_check, half="half_batch" in faults)
    if "stale" in faults:          # the round returns its state unchanged
        def stale(evaluate=False):
            runner._assemble_round()
            return {}
        runner.run_round = stale
    stage: dict = {}
    for r in range(n_check):
        runner.run_round(evaluate=False)
        runner.sync()
        if r == 0 or r == n_check - 1:
            program_readings(rt, runner, init, stage)
    stack_chips, per_chip = stack_spread(runner)
    n_compile_setup = counter.n
    prof = common.Profiler(bool(trace), w["name"])
    setup_s = time.perf_counter() - t_start

    clock = time.perf_counter
    t0 = clock()
    rounds, traced, ends = 0, 0, []
    trace_from = seconds * 0.4
    while clock() - t0 < seconds:
        if trace and prof.t0 is None and clock() - t0 >= trace_from:
            prof.start()
        with common.span("run_round"):
            runner.run_round(evaluate=False)
        with common.span("sync"):
            runner.sync()
        rounds += 1
        ends.append(clock() - t0)
        if prof.running:
            traced += 1
            if traced >= TRACED_ROUNDS:
                prof.stop()
    window = clock() - t0
    prof.stop()
    n_compile_window = counter.n - n_compile_setup
    counter.close()
    comm = runner.comm_stats
    dev = common.device_info(devs)
    end_peaks = common.memory_peaks(devs)
    runner.close()
    del runner, rt
    gc.collect()
    per = np.diff([0.0] + ends)
    print(f"set-up {setup_s:.3f} s; window: {rounds} rounds in {window:.3f} "
          f"s, per round min {per.min():.4f} median {np.median(per):.4f} "
          f"max {per.max():.4f} s; compiles in window {n_compile_window}; "
          f"uplink bytes {comm['uplink_bytes']}; peak GB a chip after the "
          f"runner's build {[round(b / 1e9, 3) for b in built_peaks]}, at "
          f"the window's end {[round(b / 1e9, 3) for b in end_peaks]}",
          file=sys.stderr, flush=True)

    # the reference, with the program's state freed
    t_ref = time.perf_counter()
    pieces = draw_pieces(wseed, slm, llm, n)
    ref = Reference(conf)
    ref_r = replay(ref, pieces, feed, job)
    print(f"reference: {n_check} rounds in {time.perf_counter() - t_ref:.1f} "
          "s", file=sys.stderr, flush=True)
    prog_r = {name: {k: (stage["mu"][name][k],
                         stage.get("change", {}).get(name, {}).get(k, 0.0))
                     for k in ref_r[name]} for name in ref_r}
    cmp_ = compare(prog_r, ref_r)
    print(f"worst leaves: {cmp_['where']}; left out "
          f"{cmp_['skipped_leaves']} leaves", file=sys.stderr, flush=True)
    # in a control run the control (the reference in lower precision)
    # stands in the program's place; the program's readings and those of
    # a fault planted in the reference (half of every batch) go beside it
    shown, extra = cmp_, []
    if prec_ctl:
        shown = compare(replay(Reference(conf, prec_ctl), pieces, feed, job),
                        ref_r)
        half = compare(replay(ref, pieces, [host_half(f) for f in feed], job),
                       ref_r)
        for tag, c in (("program", cmp_), ("half_batch", half)):
            extra += [(f"{tag}_grad_gap", c["grad_gap"], limits["grad_gap"]),
                      (f"{tag}_change_gap", c["change_gap"],
                       limits["change_gap"])]
    distinct = rows_distinct(feed)
    # a chip holds its share of the clients and less than half a client
    # more (leaves the round returns replicated, as the delivered LoRA)
    share = -(-n // w["chips"]) + 0.5
    checks = [("grad_gap", shown["grad_gap"], limits["grad_gap"]),
              ("change_gap", shown["change_gap"], limits["change_gap"]),
              ("rows_distinct", int(distinct), 1),
              ("compiles_in_window", n_compile_window, 0),
              ("stack_chips", stack_chips, w["chips"]),
              ("clients_per_chip", per_chip, share)] + extra
    correct = (shown["grad_gap"] <= limits["grad_gap"]
               and shown["change_gap"] <= limits["change_gap"] and distinct
               and stack_chips == w["chips"] and per_chip <= share)

    if trace:
        from bench import flops
        from bench import trace as trace_lib
        path = prof.xplane()
        summary = trace_lib.summarize(path, prof) if path else None
        ctx = {"cell": w["name"], "conf": conf, "traffic": job,
               "trace": summary, "device_kind": dev["kind"],
               "chips": dev["count"], "rounds_traced": traced,
               "round_flops": flops.round_flops(conf, job),
               "codec_bytes": flops.codec_bytes(conf, job)}
        metrics = common.collect_per_layer(w, ctx, summary)
        dev.update(metrics.pop("_device"))
        breakdown = metrics.pop("_breakdown")
    else:
        metrics = common.end_to_end(w["name"], {"round_s": window / rounds,
                                                "setup_s": setup_s})
        breakdown = None
    res = {"correct": bool(correct), "attempted": rounds, "failed": 0,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        res["breakdown"] = breakdown
    return res, checks


def replay(ref, pieces, feed, job) -> dict:
    """The reference's readings over the captured rounds."""
    s0 = ref.start(pieces)
    chan = RefChannel(job, ref.dtype) if job.get("channel") else None
    states = [s0]
    for f in feed:
        states.append(ref.round(states[-1], f, chan))
    return ref_readings(s0, states[1], states[-1])


def host_half(f: dict) -> dict:
    """:func:`half_batch` of a captured host feed."""
    pubs, privs, server = half_batch(((f["pub"],), (f["priv"],),
                                      f["server"]))
    return {"pub": _host(pubs[0]), "priv": _host(privs[0]),
            "server": _host(server)}


def half_batch(batches):
    """Batches whose second half repeats the first: the mean over each
    batch is then the mean over half of it (axis 1 of the server stack,
    axis 2 of the client stacks)."""
    def h(tree, axis):
        def f(v):
            B = v.shape[axis]
            idx = jnp.concatenate([jnp.arange(B // 2), jnp.arange(B // 2)])
            return jnp.take(v, idx, axis=axis)
        return jax.tree.map(f, tree)
    pubs, privs, server = batches
    return (tuple(h(p, 2) for p in pubs), tuple(h(p, 2) for p in privs),
            h(server, 1))


class RefChannel:
    """The wire of the reference round: per-tile int8 abs-max quantization
    with per-client error feedback on the uplink, norm-clipped aggregation
    (each client's whole upload clipped to the lower median of the upload
    norms), and the same quantization without feedback on the downlink."""

    def __init__(self, job: dict, dtype):
        chan = job["channel"]
        self.dtype = dtype
        self.block = chan.get("block", 128)
        self.qmax = {"int8": 127.0, "int4": 7.0}[chan["codec"]]
        self.ef = chan.get("error_feedback", True)
        self.robust = job["robust"]
        self.resid = None

    def _rt(self, x):
        flat = x.reshape(-1)
        n = flat.shape[0]
        pad = (-n) % self.block
        rows = jnp.pad(flat, (0, pad)).reshape(-1, self.block)
        scale = jnp.max(jnp.abs(rows), -1, keepdims=True) * (1.0 / self.qmax)
        q = jnp.round(rows / jnp.where(scale > 0, scale, 1.0))
        return (q * scale).reshape(-1)[:n].reshape(x.shape)

    def uplink(self, ups, w):
        n = len(ups)
        if self.resid is None:
            self.resid = [{k: jnp.zeros_like(v) for k, v in u.items()}
                          for u in ups]
        dec = []
        for j in range(n):
            x = {k: ups[j][k] + (self.resid[j][k] if self.ef else 0.0)
                 for k in ups[j]}
            d = {k: self._rt(v) for k, v in x.items()}
            if self.ef:
                self.resid[j] = {k: x[k] - d[k] for k in x}
            # the server decodes into the uploads' storage type
            dec.append({k: _store(v, self.dtype) for k, v in d.items()})
        wts = np.asarray(w, np.float64)
        if self.robust == "norm_clip":
            norms = np.array([float(jnp.sqrt(sum(jnp.sum(v * v)
                                                 for v in d.values())))
                              for d in dec])
            tau = np.sort(norms)[(n - 1) // 2]
            wts = wts / wts.sum() * np.minimum(1.0, tau / norms)
        return {k: sum(float(wts[j]) * dec[j][k] for j in range(n))
                for k in dec[0]}

    def downlink(self, tree):
        return {k: self._rt(v) for k, v in tree.items()}
