"""One run of one cell: set-up, the measured window, the readings and the
correctness check.

The harness owns the serving loop.  It drives the program's
``ContinuousBatchingEngine`` through ``submit``, ``step``,
``slot_owner``, ``pending`` and ``completions``, hands each request over
when it is due and not before, and stamps every token with the client's
clock as the loop sees it.  Nothing in the engine is patched.
"""

from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass, field
from types import SimpleNamespace

import numpy as np

from benchmarks.chip import costs, traffic, weights
from benchmarks.chip import trace as trace_mod
from benchmarks.chip.reference import dense

# the program's config fields, from the published config's keys
_PROGRAM_FIELDS = dict(
    num_hidden_layers="n_layers", hidden_size="d_model",
    num_attention_heads="n_heads", num_key_value_heads="n_kv_heads",
    intermediate_size="d_ff", vocab_size="vocab", head_dim="head_dim",
    attention_bias="qkv_bias", rope_theta="rope_theta",
    rms_norm_eps="norm_eps", sliding_window="swa_window",
    tie_word_embeddings="tie_embeddings")


class Compiles:
    """Programs compiled or loaded from the persistent cache, and the
    seconds spent tracing, lowering and compiling them, through
    ``jax.monitoring``.  One per process: listeners cannot be removed."""

    _one = None

    def __init__(self):
        import jax

        self.n = 0
        self.secs = 0.0
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    @classmethod
    def get(cls) -> "Compiles":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs
            self.names.append(str(kw.get("fun_name", "?")))
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.secs += secs

    def snap(self):
        return self.n, self.secs


@dataclass
class Rec:
    """What the client saw of one request."""
    req: traffic.Req
    times: list = field(default_factory=list)   # window-relative seconds
    reason: str = ""


class Client:
    """Hands requests to the engine and reads back its tokens."""

    def __init__(self, eng, reqs, waiting: int | None = None):
        self.eng, self.reqs, self.waiting = eng, reqs, waiting
        self.next = 0
        self.recs: dict[int, Rec] = {}
        self.steps: list[tuple[float, dict]] = []   # (end, stats delta)
        self._credited: dict[int, int] = {}
        self._base: dict[int, int] = {}
        self._live: dict[int, object] = {}
        self._done = len(eng.completions)     # earlier traffic's are not ours

    def submit_due(self, now: float) -> None:
        from repro.serving.scheduler import Request

        while self.next < len(self.reqs):
            r = self.reqs[self.next]
            if self.waiting is None:
                if r.due_s > now:
                    break
            elif len(self.eng.pending) >= self.waiting:
                break
            self.eng.submit(Request(rid=r.rid, prompt=r.prompt,
                                    max_new_tokens=r.max_new))
            self.recs[r.rid] = Rec(r)
            self.next += 1

    def next_due(self) -> float | None:
        if self.next < len(self.reqs):
            return self.reqs[self.next].due_s
        return None

    def busy(self) -> bool:
        return bool(self.eng.pending) or bool(self.eng.active_slots())

    def _credit(self, comp, t: float, done: bool) -> None:
        rid = comp.rid
        if done:
            total = len(comp.tokens)
            self.recs[rid].reason = comp.reason
        else:
            if self._live.get(rid) is not comp:
                if rid in self._live:      # preempted, then admitted again
                    self._base[rid] = self._credited[rid]
                self._live[rid] = comp
            total = self._base.get(rid, 0) + len(comp.tokens)
        new = total - self._credited.get(rid, 0)
        if new > 0:
            self.recs[rid].times.extend([t] * new)
            self._credited[rid] = total

    def harvest(self, t: float) -> None:
        for comp in self.eng.slot_owner:
            if comp is not None:
                self._credit(comp, t, done=False)
        comps = self.eng.completions
        for comp in comps[self._done:]:
            self._credit(comp, t, done=True)
        self._done = len(comps)

    def step(self, now: float, clock) -> float:
        """One engine step; returns the client's time at its end."""
        before = dict(self.eng.stats)
        self.eng.step(now)
        end = clock()
        self.steps.append((end, {k: self.eng.stats[k] - before[k]
                                 for k in before}))
        return end

    def drain(self) -> None:
        """Serve everything submitted to the end (set-up traffic)."""
        while self.next < len(self.reqs) or self.busy():
            self.submit_due(float("inf"))
            self.eng.step(0.0)
        self.harvest(0.0)


def program_config(config: dict):
    """Overrides that make the program's model the configuration's."""
    out = {}
    for key, name in _PROGRAM_FIELDS.items():
        if key in config:
            out[name] = config[key]
    return out


def build(cell, seed: int):
    """The engine as the cell runs it: the program's model at the
    configuration's sizes, the benchmark's weights from ``seed`` made on
    the device in the served dtype, the cell's pool and slots."""
    import jax

    from repro.distributed import sharding
    from repro.launch.mesh import make_serving_mesh, mesh_tp
    from repro.models import build_model

    cfg = cell.config
    mesh = make_serving_mesh(tuple(cfg["mesh"])) if "mesh" in cfg else None
    tp = 1 if mesh is None else mesh_tp(mesh)
    model = build_model(
        cfg["program_arch"], tp=tp,
        use_kernels=jax.default_backend() == "tpu",
        param_dtype=cfg["torch_dtype"], dtype=cfg["torch_dtype"],
        **program_config(cfg))
    abstract = model.init_shape()
    shardings = None
    if mesh is not None:
        shardings = sharding.named(sharding.param_specs(
            abstract, model.cfg, mesh, fsdp=False), mesh)
    params = weights.program_params(abstract, cfg, seed, shardings)
    s = cell.settings
    # an end-of-sequence id no sampled token can take (the sampler draws
    # from [0, vocab)): the engine then checks for retirement after every
    # step, as it does for any served model with an EOS, and output
    # lengths stay those the traffic draws
    eng = model.serving_engine(
        params, mesh=mesh, slots=s["slots"], max_len=s["max_len"],
        temperature=s["temperature"], seed=seed & 0x7FFFFFFF,
        page_size=s["page_size"], pages=s["pages"],
        eos_token=cfg["vocab_size"])
    return eng


def percentile(xs, q: float) -> float | None:
    return float(np.percentile(np.asarray(xs, float), q)) if xs else None


def end_to_end(client: Client, window_s: float) -> dict:
    """The user-facing readings of one window (host clock).  In an open
    loop every request due in the window counts, an unserved one with its
    wait so far; under a backlog, every request handed over."""
    if client.waiting is None:
        due = [client.recs.get(r.rid) or Rec(r) for r in client.reqs
               if r.due_s < window_s]
    else:
        due = list(client.recs.values())
    ttft, tpot, tokens = [], [], 0
    for r in due:
        ttft.append((r.times[0] if r.times else window_s) - r.req.due_s)
        tokens += len(r.times)
        if len(r.times) >= 2:
            tpot.append((r.times[-1] - r.times[0]) / (len(r.times) - 1))
    return dict(
        ttft_p90_ms=None if not ttft else percentile(ttft, 90) * 1e3,
        tpot_p90_ms=None if not tpot else percentile(tpot, 90) * 1e3,
        output_tokens_per_s=tokens / window_s,
        attempted=len(due), with_tpot=len(tpot), tokens=tokens,
        failed=sum(r.reason == "oom_pages" for r in due))


def window_work(client: Client, t0: float, t1: float):
    """Decode contexts and prefill (cached start, new tokens) of the work
    whose tokens the client saw in ``[t0, t1]``.  A step's reused prefix
    tokens are shared among that step's admissions in proportion to their
    prompts (the engine counts them per step, not per request)."""
    contexts, admits = [], {}
    for r in client.recs.values():
        plen = len(r.req.prompt)
        for j, t in enumerate(r.times):
            if t0 <= t <= t1:
                if j == 0:
                    admits.setdefault(t, []).append(plen)
                else:
                    contexts.append(plen + j)
    reused = {end: d["prefix_tokens_reused"] for end, d in client.steps}
    prefill = []
    for t, plens in admits.items():
        r, tot = reused.get(t, 0), sum(plens)
        for p in plens:
            use = min(p - 1, int(round(r * p / tot)))
            prefill.append((use, p - use))
    return contexts, prefill


def choose_sample(recs, served, seed: int, tokens: int, most: int):
    """Finished requests to check: the longest (prompt and answer), then
    others drawn from the seed until ``tokens`` served tokens or ``most``
    requests."""
    done = sorted((r for r in recs.values()
                   if r.req.rid in served and r.reason == "max_tokens"),
                  key=lambda r: r.req.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.req.prompt)
                  + len(served[r.req.rid]))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 0xC0C]).permutation(len(rest))
    out, n = [longest], len(served[longest.req.rid])
    for i in order:
        if n >= tokens or len(out) >= most:
            break
        out.append(rest[i])
        n += len(served[rest[i].req.rid])
    return out


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def setup_traffic(eng, cell, seed: int, docs) -> None:
    """Set-up through the engine: every program shape that the mix's
    prompts reach (buckets up to the one of its longest prompt, and each
    tail pair below it) and, for a mix with shared documents, the index
    filled with them (least popular first, so the popular ones are the
    most recent).  A request preempted and readmitted past that bucket
    would still compile in the window; ``launcher.window_compiles``
    shows it."""
    vocab = cell.config["vocab_size"]
    longest = traffic.longest_prompt(cell.mix)
    top = next(b for b in eng.buckets if b >= longest)
    warm = traffic.shape_warmup([b for b in eng.buckets if b <= top],
                                eng.max_len, vocab, seed ^ 0x5EED5EED,
                                rid0=0)
    # the first request alone: after its decode burst the engine's
    # sampling key lives where the decode step puts it (replicated over a
    # mesh), and every later program compiles for that key
    Client(eng, warm[:1]).drain()
    Client(eng, warm[1:]).drain()
    fill = [traffic.Req(len(warm) + i, 0.0, tuple(d), 1)
            for i, d in enumerate(reversed(docs))]
    if fill:
        Client(eng, fill).drain()
    eng.reset_stats()


def device_info(devices) -> dict:
    d = devices[0]
    peak = 0
    for dev in devices:
        st = dev.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return dict(platform=d.platform, kind=d.device_kind,
                count=len(devices), memory_peak_bytes=peak)


def serve_window(client: Client, seconds: float, t0: float, trace_dir=None,
                 trace_len: float = 0.0):
    """The measured window, from ``t0`` on the host clock: hand each
    request over when due, step the engine while it has work, stamp the
    tokens.  With ``trace_dir``, the profiler traces the window's last
    ``trace_len`` seconds.  Returns the window's length and the traced
    interval (window-relative, or None)."""
    import shutil

    import jax

    clock = time.perf_counter
    rel = lambda: clock() - t0          # noqa: E731
    traced = window_span = None
    while True:
        now = rel()
        if now >= seconds:
            break
        if (trace_dir is not None and traced is None
                and now >= seconds - trace_len):
            if trace_dir.exists():
                shutil.rmtree(trace_dir)
            jax.profiler.start_trace(str(trace_dir))
            window_span = _annotate("bench.window")
            window_span.__enter__()
            traced = [rel(), None]
        with _annotate("bench.submit"):
            client.submit_due(now)
        if client.busy():
            with _annotate("bench.step"):
                end = client.step(now, rel)
            with _annotate("bench.harvest"):
                client.harvest(end)
        else:
            nxt = client.next_due()
            with _annotate("bench.idle"):
                time.sleep(max(0.0, min(seconds if nxt is None else nxt,
                                        seconds) - now))
    window_s = rel()
    if traced is not None:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced[1] = window_s
    return window_s, traced


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        trace_dir, control: bool = False) -> dict:
    """One run.  ``t_start``: the process's start on the host clock.
    ``control`` also puts the precision control in the program's place
    on the same sample and adds its verdict under ``"control"``
    (calibration only; a run of the benchmark never does)."""
    import jax

    from benchmarks.chip import peaks as peaks_mod

    clock = time.perf_counter
    compiles = Compiles.get()
    s = cell.settings
    devices = jax.devices()[:cell.chips]
    c0 = compiles.snap()
    eng = build(cell, seed)
    tr = traffic.generate(cell.mix, s, seed, cell.config["vocab_size"],
                          seconds)
    setup_traffic(eng, cell, seed, tr.docs)
    c1 = compiles.snap()
    backlog = cell.mix["arrivals"] == "backlog"
    client = Client(eng, tr.reqs, cell.mix["waiting"] if backlog else None)
    trace_len = min(seconds, s.get("trace_seconds", 5.0)) if trace else 0.0
    t0 = clock()
    setup_s = t0 - t_start
    window_s, traced = serve_window(client, seconds, t0,
                                    trace_dir if trace else None, trace_len)
    c2 = compiles.snap()
    if c2[0] > c1[0]:
        print("benchmark: compiled in the window: "
              + ", ".join(compiles.names[c1[0]:c2[0]]), file=sys.stderr)
    stats = dict(eng.stats)
    served = {c.rid: list(c.tokens) for c in eng.completions}
    dev = device_info(devices)
    e2e = end_to_end(client, window_s)
    client.eng = None
    del eng
    gc.collect()

    ctx = SimpleNamespace(
        cell=cell, m=weights.dims(cell.config), chips=cell.chips,
        peaks=peaks_mod.peaks(dev["kind"]) if dev["platform"] == "tpu"
        else None,
        client=client, window_s=window_s, e2e=e2e, stats=stats,
        setup_s=setup_s, setup_compile_s=c1[1] - c0[1],
        window_compiles=c2[0] - c1[0], trace=None, traced=traced,
        work=None, costs=costs)
    result = {}
    if traced is not None:
        raw = trace_mod.extract(trace_dir)
        if raw["devices"]:
            ctx.trace = trace_mod.reduce(raw)
    if ctx.trace is not None:
        ctx.work = window_work(client, *traced)
        dev["busy_s"] = ctx.trace["busy_mean_s"]
        dev["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = trace_mod.breakdown(ctx.trace)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        v = cell.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    if control:
        ctl_checks, ctl_correct = check(cell, seed, client, served, True)
        result["control"] = dict(correct=ctl_correct, checks=ctl_checks)
    checks, correct = check(cell, seed, client, served)
    engine = {k: stats[k] for k in ("admitted", "preempted", "steps",
                                     "prefix_hits", "peak_pages")}
    result = dict(correct=correct, attempted=e2e["attempted"],
                  failed=e2e["failed"], metrics=metrics, device=dev,
                  **result, engine=engine, checks=checks)
    return result


def check(cell, seed: int, client: Client, served: dict,
          control: bool = False):
    """Compare what the timed path served with the plain reference.
    With ``control`` the precision control takes the program's place: the
    tokens compared are those that the float8 reference puts first at
    each position of the same sample, under the same limits."""
    s = cell.settings
    sample = choose_sample(client.recs, served, seed,
                           s["check_tokens"], s["check_requests"])
    n_tok = sum(len(served[r.req.rid]) for r in sample)
    gap = None                        # nothing finished: nothing compared
    if sample:
        fwd = dense.Forward(cell.config, seed)
        gaps, ctl = dense.served_gaps(
            fwd, [r.req.prompt for r in sample],
            [served[r.req.rid] for r in sample], control=control)
        gap = max(ctl if control else gaps)
    checks = {
        "max_logit_gap": {"value": gap, "limit": s["max_logit_gap"]},
        "tokens_checked": {"value": n_tok, "limit": s["check_tokens"]},
    }
    correct = (gap is not None and gap <= s["max_logit_gap"]
               and n_tok >= s["check_tokens"])
    return checks, correct
