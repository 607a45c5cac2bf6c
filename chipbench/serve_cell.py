"""Serve cells: ``ServingEngine.submit`` / ``ServingEngine.step`` over an
``AdapterRegistry`` of seeded rank-r adapters.

``"loop": "closed"``: ``clients`` callers each wait for their reply and
then send the next request; set-up compiles the decode step and fills
the slots, and the window ends with the first step after ``--seconds``.
``"loop": "open"``: requests arrive on a Poisson schedule at ``rate`` per
second for ``--seconds``; each is timed from when it was due, and those
due late in the window are followed to completion after it closes.

The benchmark keeps its own clock of each request: its due time and the
time each of its output tokens came back from ``step()``. Once the
window has closed the program is freed and the reference runs each
request of a seeded sample, the longest among them, over its prompt and
served tokens; the number compared is the widest gap by which a served
token's logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, reference as R, traffic, weights
from chipbench.round_cell import check_tree, program_cfg


def setup(cell):
    from repro.models import transformer as T
    from repro.serving import AdapterRegistry, ServingEngine

    m, w = cell.model, cell.params
    cfg = program_cfg(m)
    key = jax.random.PRNGKey(0)
    base = weights.base_params(m, cell.seed)
    check_tree(base, cfg, lambda: T.init_params(cfg, key))
    stacked = weights.tenant_adapters(m, cell.seed, w["lora_rank"],
                                      w["n_adapters"])
    one = jax.tree.map(lambda a: a[0], stacked)
    check_tree(one, cfg, lambda: T.init_lora(cfg, key, w["lora_rank"]))
    registry = AdapterRegistry(one, w["n_adapters"])
    for i in range(w["n_adapters"]):
        registry.add(f"tenant/{i}", jax.tree.map(lambda a: a[i], stacked))
    engine = ServingEngine(cfg, base, adapters=registry,
                           n_slots=w["n_slots"], kv_capacity=w["kv_capacity"])
    engine.warmup()
    return engine, base, stacked


class Book:
    """The benchmark's own record of each request and each step."""

    def __init__(self, engine):
        self.engine = engine
        self.due, self.times, self.reqs = {}, {}, {}
        self.steps = []              # (wall_s, n_active, sum of kv lengths)

    def submit(self, job, due: float):
        req = self.engine.submit(job.prompt, max_new_tokens=job.max_new,
                                 adapter=f"tenant/{job.adapter}")
        self.due[req.rid], self.times[req.rid] = due, []
        self.reqs[req.rid] = (req, job.adapter)
        return req

    def step(self):
        """One engine step; returns the requests it finished."""
        eng = self.engine
        t0 = time.perf_counter()
        with harness.span("admit_and_step"):
            eng._admit()
            active = list(eng.scheduler.active)
            before = [len(r.generated) for _, r in active]
            kv = sum((r.cursor if r.cursor < r.prompt_len
                      else r.prompt_len + len(r.generated) - 1) + 1
                     for _, r in active)
            done = eng.step()
        now = time.perf_counter()
        self.steps.append((now - t0, len(active), kv))
        for (_, r), n in zip(active, before):
            if len(r.generated) > n:
                self.times[r.rid].append(now)
        return done


def _drive(cell, book, m, w, meter) -> dict:
    run = _closed if cell.workload["loop"] == "closed" else _open
    return run(cell, book, m, w, meter)


def run(cell, devices, meter) -> dict:
    m, w = cell.model, cell.params
    engine, base, stacked = setup(cell)
    book = Book(engine)
    out = _drive(cell, book, m, w, meter)
    out["memory_peak_bytes"] = harness.memory_peak_bytes(devices)
    finished = [book.reqs[r.rid] for r in engine.finished
                if r.rid in book.reqs]
    del engine, book
    gc.collect()
    gap = check_served(m, w, base, stacked, finished, cell.seed)["logit_gap"]
    out["checks"] = {"logit_gap": (gap, w.get("limits", {}).get("logit_gap"))}
    return out


def readings(cell, devices, control: bool, meter) -> list:
    """The run's end-to-end numbers and its ``logit_gap``, and with
    ``control`` the control's (the reference with its base rounded to
    float8 in the engine's place), for ``calibrate.py``."""
    m, w = cell.model, cell.params
    engine, base, stacked = setup(cell)
    book = Book(engine)
    res = _drive(cell, book, m, w, meter)
    finished = [book.reqs[r.rid] for r in engine.finished
                if r.rid in book.reqs]
    out = [("e2e", res["e2e"]), ("peak", harness.memory_peak_bytes(devices))]
    del engine, book
    gc.collect()
    out.append(("program", check_served(m, w, base, stacked, finished,
                                        cell.seed)))
    if control:
        out.append(("control", check_served(m, w, base, stacked, finished,
                                            cell.seed, control=True)))
    del base, stacked
    jax.clear_caches()
    return out


def _closed(cell, book, m, w, meter) -> dict:
    pool = traffic.jobs(w, m["vocab_size"], w["pool"], cell.seed,
                        block=w["clients"])
    nxt = 0
    for _ in range(w["clients"]):
        book.submit(pool[nxt % len(pool)], 0.0)
        nxt += 1
    with harness.span("fill"):
        book.step()
    n_steps0 = len(book.steps)
    with cell.window(meter):
        while True:
            for _ in book.step():
                book.submit(pool[nxt % len(pool)], 0.0)
                nxt += 1
            if time.perf_counter() - cell.t_window >= cell.seconds:
                break
    t1 = cell.t_window + cell.window_s
    tokens = sum(1 for ts in book.times.values() for t in ts
                 if cell.t_window <= t <= t1)
    done = sum(1 for r, _ in book.reqs.values()
               if r.done and cell.t_window <= r.t_finish <= t1)
    steps = book.steps[n_steps0:]
    return {"e2e": {"setup_s": cell.setup_s,
                    "serve_tokens_per_s": tokens / cell.window_s,
                    "requests_per_s": done / cell.window_s},
            "counts": _step_counts(steps),
            "attempted": nxt, "failed": 0}


def _open(cell, book, m, w, meter) -> dict:
    n = int(round(w["rate"] * cell.seconds))
    plan = traffic.jobs(w, m["vocab_size"], n, cell.seed, rate=w["rate"])
    i = 0
    eng = book.engine
    # admission's own program (the slot reset) compiles on first use
    eng.submit(plan[0].prompt[:1], max_new_tokens=1, adapter="tenant/0")
    with harness.span("fill"):
        while eng.has_work():
            eng.step()
    with cell.window(meter):
        t0 = cell.t_window
        while i < n or eng.has_work():
            now = time.perf_counter()
            while i < n and t0 + plan[i].due <= now:
                book.submit(plan[i], t0 + plan[i].due)
                i += 1
            if eng.has_work():
                book.step()
            elif i < n:
                with harness.span("wait_arrival"):
                    time.sleep(max(0.0, t0 + plan[i].due
                                   - time.perf_counter()))
    t_end = t0 + cell.seconds
    rids = list(book.due)
    ttft, itl, queue, tokens = [], [], [], 0
    failed = 0
    for rid in rids:
        ts, req = book.times[rid], book.reqs[rid][0]
        tokens += sum(1 for t in ts if t <= t_end)
        if not req.done or not ts:
            failed += 1
            ttft.append(float("inf"))
            continue
        ttft.append(ts[0] - book.due[rid])
        itl.extend(np.diff(ts).tolist())
        queue.append(req.t_admit - book.due[rid])
    counts = _step_counts(book.steps)
    counts.update(queue_p95_ms=_p95_ms(queue) if queue else None,
                  n_requests=n)
    return {"e2e": {"setup_s": cell.setup_s,
                    "serve_tokens_per_s": tokens / cell.seconds,
                    "ttft_p95_ms": _p95_ms(ttft), "itl_p95_ms": _p95_ms(itl)},
            "counts": counts, "attempted": n, "failed": failed}


def _p95_ms(seconds) -> float:
    return float(np.percentile(np.asarray(seconds), 95)) * 1e3


def _step_counts(steps) -> dict:
    return {"steps": len(steps),
            "step_wall_s": float(sum(s[0] for s in steps)),
            "slot_tokens": int(sum(s[1] for s in steps)),
            "kv_tokens": int(sum(s[2] for s in steps))}


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------


def sample(finished, seed: int, n: int):
    """The finished request with the most served tokens, and ``n - 1``
    more drawn from the seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -len(finished[i][0].generated))
    rng = np.random.default_rng([seed, 0xC0DE])
    rest = rng.permutation(order[1:])[: n - 1]
    return [finished[order[0]]] + [finished[i] for i in rest]


def check_served(m, w, base, stacked, finished, seed, *, control=False):
    """Widest gap, over the sample's served positions, between the
    reference's best logit and that of the token the program served (or,
    for the control, the token the float8 reference puts first)."""
    picks = sample(finished, seed, w["check_requests"])
    cap = w["kv_capacity"]
    v = m["vocab_size"]
    params_c = R.quantize(base) if control else None

    @jax.jit
    def ref_logits(params, lora, tokens):
        h, _ = R.hidden(m, params, lora, tokens[None])
        return R.logits(m, params, h)[0, :, :v]

    gap, served = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for req, row in picks:
            toks = np.concatenate([req.prompt, np.asarray(req.generated[:-1],
                                                          np.int32)])
            padded = np.zeros((cap,), np.int32)
            padded[: len(toks)] = toks
            lora = jax.tree.map(lambda a: a[row], stacked)
            pos = slice(req.prompt_len - 1, len(toks))
            want = np.asarray(ref_logits(base, lora, jnp.asarray(padded)))[pos]
            if control:
                got = np.asarray(ref_logits(params_c, lora,
                                            jnp.asarray(padded)))[pos]
                chosen = np.argmax(got, axis=-1)
            else:
                chosen = np.asarray(req.generated)
            g = want.max(-1) - want[np.arange(len(chosen)), chosen]
            gap = max(gap, float(g.max()))
            served += len(chosen)
    return {"logit_gap": gap, "requests": len(picks), "served": served}
