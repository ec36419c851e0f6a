"""Strategy search engine: analyse → candidates → dry-run → pick.

Equivalent capability: reference atorch AccelerationEngine
(atorch/atorch/auto/engine/acceleration_engine.py:13) with its Executor/
task loop (engine/executor.py:36), optimization-method library and search
algorithms (combination + Bayesian SG, engine/sg_algo/), and the dry-runner
that profiles fwd/bwd to score strategies
(atorch/auto/dry_runner/dry_runner.py).

TPU redesign: a candidate is a complete :class:`Strategy` (mesh
factorization × remat × precision). "Dry-running" compiles the jitted
train step for the candidate on small shapes and times real steps —
compilation cost is the search cost; there is no module rewriting to
undo between candidates. Memory feasibility is pre-filtered analytically
so only plausible meshes are compiled.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

from dlrover_tpu.common.log import get_logger
from dlrover_tpu.parallel.mesh import MeshConfig
from dlrover_tpu.parallel.strategy import Strategy, auto_strategy

logger = get_logger(__name__)

# default relative loss tolerance for selecting a quantized dtype
LOSS_PARITY_TOL = 0.05


# --------------------------------------------------------------------------
# analyser (reference auto/analyser/analyser.py:14)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ModelAnalysis:
    """Static model facts the planner needs."""

    param_count: int = 0
    param_bytes: int = 0
    largest_layer_params: int = 0
    has_attention: bool = False
    n_layers: int = 0
    moe: bool = False
    n_experts: int = 1
    hidden: int = 0  # model width (activation feature dim)


def analyse_params(params) -> ModelAnalysis:
    """Derive ModelAnalysis from a params pytree (or its eval_shape).

    ``hidden`` is inferred structurally instead of hard-coded: for each
    weight matrix the smaller of its two trailing dims is a candidate
    for the residual width (projections map hidden->heads/mlp and back,
    so hidden shows up on one side of nearly every matmul); the modal
    candidate wins. Callers can still override via the estimator's
    ``hidden=`` argument.
    """
    import collections

    import jax
    import numpy as np

    leaves = jax.tree.leaves(params)
    count = 0
    bytes_ = 0
    largest = 0
    width_votes: collections.Counter = collections.Counter()
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            continue
        n = int(np.prod(shape)) if shape else 1
        count += n
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", 4)
        bytes_ += n * itemsize
        largest = max(largest, n)
        if len(shape) >= 2:
            width_votes[int(min(shape[-2], shape[-1]))] += 1
    # stacked-layer detection: a leading dim shared by many leaves
    n_layers = 0
    for leaf in leaves:
        shape = getattr(leaf, "shape", ())
        if len(shape) >= 3:
            n_layers = max(n_layers, shape[0])
    hidden = width_votes.most_common(1)[0][0] if width_votes else 0
    return ModelAnalysis(
        param_count=count,
        param_bytes=bytes_,
        largest_layer_params=largest,
        n_layers=n_layers,
        hidden=hidden,
    )


# --------------------------------------------------------------------------
# memory feasibility (analytic pre-filter)
# --------------------------------------------------------------------------


def estimate_hbm_per_device(
    analysis: ModelAnalysis,
    strategy: Strategy,
    batch_per_device: int = 8,
    seq_len: int = 2048,
    hidden: int | None = None,
    attn_quadratic: bool = False,
) -> float:
    """Rough bytes/device: params + grads + Adam state + activations.

    Model-state is sharded by fsdp×tensor×expert (GSPMD ZeRO-3 analogue);
    activations by data×fsdp×seq with remat discounts. ``hidden``
    defaults to the width inferred by :func:`analyse_params` so the
    activation term tracks the actual model instead of a fixed 4096.

    The activation term charges the tensors the backward actually
    stores per layer — attention q/k/v/o (4 x hidden wide), the MLP
    gate/up hidden (~3 x hidden each) and the two norm inputs — not a
    single hidden-wide tensor per layer; at long context the attention
    residuals dominate and a single-tensor estimate green-lights
    infeasible meshes that then burn a full compile in the dry-runner.
    ``attn_quadratic=True`` additionally charges the [B, H, S, S] score
    materialisation of non-blockwise attention (the reference-einsum
    path; the Pallas flash kernels keep scores in VMEM tiles).
    """
    if hidden is None:
        hidden = analysis.hidden or 4096
    m = strategy.mesh
    model_shard = max(m.fsdp * m.tensor * m.expert * m.pipe, 1)
    # fp32 master params + grads + 2x Adam moments
    model_state = analysis.param_count * 4.0 * 4.0 / model_shard
    # "offload" keeps only the full-level boundary tensors in HBM (the
    # minimal-level dot saves live in pinned host memory)
    act_discount = {
        "none": 1.0, "minimal": 0.35, "offload": 0.15, "full": 0.12,
    }.get(strategy.remat, 0.35)
    act_shard = max(m.seq, 1)
    # stored per layer (bf16): residual + 2 norm inputs (3x hidden),
    # q/k/v/o (4x hidden), gate/up hidden (~2 x 3x hidden) + lse rows
    width_factor = 3.0 + 4.0 + 6.0
    acts = (
        batch_per_device * seq_len * hidden * 2.0 * width_factor
        * max(analysis.n_layers, 1)
        * act_discount
        / act_shard
    )
    if attn_quadratic:
        heads = max(hidden // 128, 1)
        # fp32 scores per layer, both operands sequence-sharded (ring
        # attention holds S_local x S_local blocks per step)
        acts += (
            batch_per_device * heads * (seq_len / act_shard) ** 2 * 4.0
            * max(analysis.n_layers, 1) * act_discount
        )
    return model_state + acts


# --------------------------------------------------------------------------
# candidate generation (combination search-algorithm analogue)
# --------------------------------------------------------------------------


def _factorizations(n: int, dims: int):
    """All tuples (d0..dims-1) with product n, each di >= 1 dividing n."""
    if dims == 1:
        yield (n,)
        return
    for d in [x for x in range(1, n + 1) if n % x == 0]:
        for rest in _factorizations(n // d, dims - 1):
            yield (d,) + rest


def _dcn_placement(pipe: int, data: int, fsdp: int, n_slices: int):
    """Distribute ``n_slices`` across the DCN-tolerant axes, cheapest
    traffic first: pipe (p2p stage activations) > data (one grad
    allreduce/step) > fsdp (adds per-step param all-gather over DCN).
    Returns (dcn_pipe, dcn_data, dcn_fsdp) or None if the factorization
    cannot absorb all slices."""
    import math as _math

    remaining = n_slices
    placement = []
    for size in (pipe, data, fsdp):
        f = _math.gcd(size, remaining)
        placement.append(f)
        remaining //= f
    if remaining != 1:
        return None
    return tuple(placement)


def candidate_strategies(
    n_devices: int,
    analysis: ModelAnalysis,
    devices_per_host: int = 4,
    hbm_gb: float = 16.0,
    seq_len: int = 2048,
    batch_per_device: int = 8,
    hidden: int | None = None,
    max_candidates: int = 16,
    allow_pipe: bool = True,
    n_slices: int = 1,
    ici_gbps: float = 180.0,
    dcn_gbps: float = 25.0,
) -> list[Strategy]:
    """Enumerate feasible mesh factorizations, best-first.

    Ordering heuristics (TPU cost model):
    - prefer pure-FSDP (best compute:comm on ICI, no constraints),
    - then tensor ≤ devices_per_host (TP collectives stay on-host ICI),
    - pipe only when allowed and layers are stacked,
    - discard meshes whose HBM estimate exceeds capacity.

    Multi-slice (``n_slices > 1``, the reference's cross-node scale —
    atorch distributed.py:321 nested node-level groups): every candidate
    must place the slice boundary on DCN-tolerant axes (pipe/data/fsdp;
    tensor/seq/expert collectives are per-layer and must stay on ICI).
    The cost model charges DCN traffic by the ICI:DCN bandwidth
    asymmetry (``ici_gbps/dcn_gbps``, default v5e-ish 180:25): pipeline
    stages pay least (p2p activations), data next (one gradient
    allreduce per step), fsdp most (adds the param all-gather to every
    step).
    """
    hbm = hbm_gb * (1 << 30)
    bw_ratio = max(ici_gbps / max(dcn_gbps, 1e-9), 1.0)
    seen: set = set()
    out: list[tuple[float, Strategy]] = []
    for data, fsdp, tensor, pipe in _factorizations(n_devices, 4):
        if tensor > devices_per_host:
            continue
        if pipe > 1 and (not allow_pipe or analysis.n_layers < pipe):
            continue
        if pipe > 8:
            continue
        key = (data, fsdp, tensor, pipe)
        if key in seen:
            continue
        seen.add(key)
        dcn_pipe = dcn_data = dcn_fsdp = 1
        dcn_cost = 0.0
        if n_slices > 1:
            placed = _dcn_placement(pipe, data, fsdp, n_slices)
            if placed is None:
                continue  # slice boundary would cut an ICI-only axis
            dcn_pipe, dcn_data, dcn_fsdp = placed
            import math as _math

            dcn_cost = (
                0.01 * _math.log2(dcn_pipe)
                + 0.06 * _math.log2(dcn_data)
                + 0.15 * _math.log2(dcn_fsdp)
            ) * (bw_ratio / 7.0)
        mesh = MeshConfig(
            pipe=pipe, data=data, fsdp=fsdp, expert=1, seq=1,
            tensor=tensor, dcn_pipe=dcn_pipe, dcn_data=dcn_data,
            dcn_fsdp=dcn_fsdp,
        )
        # cheapest-compute first: the first memory-feasible remat level
        # wins ('none' is fastest when it fits)
        for remat in ("none", "minimal", "offload", "full"):
            s = Strategy(mesh=mesh, remat=remat)
            est = estimate_hbm_per_device(
                analysis, s, batch_per_device, seq_len, hidden
            )
            if est > hbm * 0.9:
                continue
            # cost-model score (lower better): comm penalty for tensor/
            # pipe, remat recompute penalty, replication penalty for data
            score = (
                0.15 * (tensor > 1)
                + 0.05 * tensor / devices_per_host
                + 0.25 * (pipe > 1)
                + 0.02 * pipe
                + {"none": 0.0, "minimal": 0.05, "offload": 0.10,
                   "full": 0.15}[remat]
                + 0.10 * (data > 1 and fsdp == 1)  # pure DP replicates
                + dcn_cost
            )
            out.append((score, s))
            break  # cheapest feasible remat for this mesh only
    out.sort(key=lambda t: t[0])
    strategies = [s for _, s in out[:max_candidates]]

    # long-context variants: move part of the fsdp axis onto seq (ring
    # attention) for sequences past the single-shard threshold
    if seq_len >= 32768:
        extra = []
        for s in strategies[:4]:
            m = s.mesh
            want = max(seq_len // 32768, 2)
            seq = 1
            for cand in range(min(want, m.fsdp), 1, -1):
                if m.fsdp % cand == 0:
                    seq = cand
                    break
            if seq > 1 and (m.fsdp // seq) % m.dcn_fsdp == 0:
                extra.append(Strategy(
                    mesh=MeshConfig(
                        pipe=m.pipe, data=m.data, fsdp=m.fsdp // seq,
                        expert=1, seq=seq, tensor=m.tensor,
                        dcn_pipe=m.dcn_pipe, dcn_data=m.dcn_data,
                        dcn_fsdp=m.dcn_fsdp,
                    ),
                    remat=s.remat,
                ))
        strategies = extra + strategies

    # MoE variants: carve an expert axis out of fsdp
    if analysis.moe and analysis.n_experts > 1:
        extra = []
        for s in strategies[:4]:
            m = s.mesh
            exp = 1
            for cand in range(min(analysis.n_experts, m.fsdp), 1, -1):
                if m.fsdp % cand == 0:
                    exp = cand
                    break
            if exp > 1 and (m.fsdp // exp) % m.dcn_fsdp == 0:
                extra.append(Strategy(
                    mesh=MeshConfig(
                        pipe=m.pipe, data=m.data, fsdp=m.fsdp // exp,
                        expert=exp, seq=m.seq, tensor=m.tensor,
                        dcn_pipe=m.dcn_pipe, dcn_data=m.dcn_data,
                        dcn_fsdp=m.dcn_fsdp,
                    ),
                    remat=s.remat,
                ))
        strategies = extra + strategies

    return strategies[:max_candidates]


# --------------------------------------------------------------------------
# dry-runner (reference auto/dry_runner/dry_runner.py)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class DryRunResult:
    strategy: Strategy
    compile_s: float = 0.0
    step_s: float = 0.0
    ok: bool = True
    error: str = ""
    # final measured loss (None when the step returns no "loss" metric):
    # the quantized-dtype selection gate compares it against the same
    # mesh's unquantized run before an int8 candidate may win
    loss: Optional[float] = None


class DryRunner:
    """Compiles + times the real jitted train step for a candidate."""

    def __init__(self, build_fn: Callable[[Strategy], tuple],
                 warmup: int = 1, iters: int = 3):
        """``build_fn(strategy) -> (train_step, state, batch, rng)``."""
        self._build_fn = build_fn
        self._warmup = warmup
        self._iters = iters

    def profile(self, strategy: Strategy) -> DryRunResult:
        import jax

        result = DryRunResult(strategy=strategy)
        try:
            t0 = time.perf_counter()
            train_step, state, batch, rng = self._build_fn(strategy)
            state, _ = train_step(state, batch, rng)
            jax.block_until_ready(state)
            result.compile_s = time.perf_counter() - t0
            for _ in range(self._warmup):
                state, _ = train_step(state, batch, rng)
            jax.block_until_ready(state)
            t1 = time.perf_counter()
            for _ in range(self._iters):
                state, metrics = train_step(state, batch, rng)
            jax.block_until_ready(state)
            result.step_s = (time.perf_counter() - t1) / self._iters
            try:
                result.loss = float(metrics.get("loss"))
            except (TypeError, AttributeError):
                pass
        except Exception as e:  # noqa: BLE001 - infeasible candidate
            result.ok = False
            result.error = f"{type(e).__name__}: {e}"
            logger.warning(
                "dry-run failed for %s: %s", strategy.describe(),
                result.error[:200],
            )
        return result


def cost_model_rank_correlation(
    candidates: list[Strategy], results: list["DryRunResult"],
) -> float | None:
    """Spearman rank correlation between the cost-model ordering (the
    candidates list is emitted best-first) and measured step times.

    The cost-model weights are tie-breaker heuristics; this validates
    them against dry-run truth after every search — a correlation near
    zero (or negative) means the analytic model is misleading the
    search on this hardware/model and its ordering should not be
    trusted beyond memory feasibility. Returns None with <3 usable
    points."""
    index_of = {id(s): i for i, s in enumerate(candidates)}
    pairs = [
        (index_of[id(r.strategy)], r.step_s)
        for r in results
        if r.ok and id(r.strategy) in index_of
    ]
    if len(pairs) < 3:
        return None
    ranks_model = _ranks([p[0] for p in pairs])
    ranks_meas = _ranks([p[1] for p in pairs])
    # Pearson on the (fractional) ranks — the tie-correct Spearman form;
    # zero variance (e.g. all measurements tied) carries no ordering
    # signal at all, so report None rather than a fake correlation
    n = len(pairs)
    m1 = sum(ranks_model) / n
    m2 = sum(ranks_meas) / n
    cov = sum(
        (a - m1) * (b - m2) for a, b in zip(ranks_model, ranks_meas)
    )
    v1 = sum((a - m1) ** 2 for a in ranks_model)
    v2 = sum((b - m2) ** 2 for b in ranks_meas)
    if v1 <= 0 or v2 <= 0:
        return None
    return cov / (v1 * v2) ** 0.5


def _ranks(values: list) -> list[float]:
    """Fractional (average) ranks: ties share their mean rank, as
    Spearman requires — otherwise equal measurements would inherit
    list-order ranks and fake a perfect correlation."""
    order = sorted(range(len(values)), key=lambda i: values[i])
    ranks = [0.0] * len(values)
    i = 0
    while i < len(order):
        j = i
        while j + 1 < len(order) and \
                values[order[j + 1]] == values[order[i]]:
            j += 1
        avg = (i + j) / 2.0
        for k in range(i, j + 1):
            ranks[order[k]] = avg
        i = j + 1
    return ranks


# --------------------------------------------------------------------------
# Bayesian-optimization search generator
# (reference atorch/auto/engine/sg_algo/bayes_opt_sg.py with its vendored
#  HEBO — TPU redesign: a small numpy Gaussian process + expected
#  improvement over the discrete candidate space, step time from the
#  dry-runner as the objective; no vendored library needed)
# --------------------------------------------------------------------------


def _strategy_features(s: Strategy):
    """Embed a candidate in R^8 for the GP kernel: log2 mesh dims +
    remat ordinal (scaled so one mesh-halving ~ one remat level) +
    DCN exposure."""
    import math

    m = s.mesh
    remat_ord = {
        "none": 0.0, "minimal": 1.0, "offload": 1.5, "full": 2.0,
    }.get(s.remat, 1.0)
    return [
        math.log2(max(m.data, 1)),
        math.log2(max(m.fsdp, 1)),
        math.log2(max(m.tensor, 1)),
        math.log2(max(m.pipe, 1)),
        math.log2(max(m.seq, 1)),
        math.log2(max(m.expert, 1)),
        remat_ord,
        # DCN exposure: slices crossed by bandwidth-hungry axes dominate
        # the comm profile, so they get their own GP dimension
        math.log2(max(m.dcn_data * m.dcn_fsdp, 1))
        + 0.5 * math.log2(max(m.dcn_pipe, 1)),
    ]


class BayesianSearch:
    """GP + expected-improvement over a discrete candidate list.

    Candidates arrive cost-model-ordered (best guess first), which seeds
    the search: the first ``n_seed`` evaluations take the top-ranked and
    the most-distant candidate, then EI picks each next dry-run. Failed
    dry-runs feed back as a large penalty so the GP steers away from
    that region instead of retrying neighbours.
    """

    def __init__(self, candidates: list[Strategy], n_seed: int = 2,
                 noise: float = 1e-6, length_scale: float = 1.5):
        import numpy as np

        self._candidates = list(candidates)
        self._X = np.asarray(
            [_strategy_features(s) for s in self._candidates], float
        )
        self._observed: dict[int, float] = {}
        self._failed: set[int] = set()
        self._noise = noise
        self._ls = length_scale
        self._seed_order = self._make_seed_order(n_seed)

    def _make_seed_order(self, n_seed: int) -> list[int]:
        import numpy as np

        if not self._candidates:
            return []
        order = [0]
        if n_seed > 1 and len(self._candidates) > 1:
            d = np.linalg.norm(self._X - self._X[0], axis=1)
            order.append(int(d.argmax()))
        return order[:n_seed]

    def _kernel(self, A, B):
        import numpy as np

        d2 = ((A[:, None, :] - B[None, :, :]) ** 2).sum(-1)
        return np.exp(-0.5 * d2 / (self._ls**2))

    def suggest(self, exclude=()) -> int | None:
        """Index of the next candidate to dry-run (None = exhausted).
        ``exclude``: indices already handed out but not yet observed
        (in-flight dry-runs in the task-loop API)."""
        import numpy as np

        skip = set(self._observed) | set(exclude)
        unobserved = [
            i for i in range(len(self._candidates)) if i not in skip
        ]
        if not unobserved:
            return None
        for i in self._seed_order:
            if i not in skip:
                return i
        obs_idx = sorted(self._observed)
        if not obs_idx:
            # seeds all in flight, nothing observed yet (concurrent
            # task-loop callers): hand out cost-model order
            return unobserved[0]
        X_o = self._X[obs_idx]
        y = np.asarray([self._observed[i] for i in obs_idx], float)
        y_mean, y_std = y.mean(), max(y.std(), 1e-9)
        y_n = (y - y_mean) / y_std
        K = self._kernel(X_o, X_o) + self._noise * np.eye(len(obs_idx))
        L = np.linalg.cholesky(K)
        alpha = np.linalg.solve(L.T, np.linalg.solve(L, y_n))
        X_u = self._X[unobserved]
        K_s = self._kernel(X_u, X_o)
        mu = K_s @ alpha
        v = np.linalg.solve(L, K_s.T)
        var = np.clip(1.0 - (v**2).sum(0), 1e-12, None)
        sigma = np.sqrt(var)
        # expected improvement (minimization)
        best = y_n.min()
        z = (best - mu) / sigma
        from math import erf, sqrt

        cdf = 0.5 * (1.0 + np.vectorize(erf)(z / sqrt(2.0)))
        pdf = np.exp(-0.5 * z**2) / np.sqrt(2 * np.pi)
        ei = (best - mu) * cdf + sigma * pdf
        return unobserved[int(ei.argmax())]

    def observe(self, index: int, step_s: float, ok: bool = True):
        if not ok:
            # penalty anchored to the worst *successful* time so
            # repeated failures don't compound 10x each and blow up the
            # GP's normalization
            ok_times = [
                v for i, v in self._observed.items()
                if i not in self._failed
            ]
            step_s = max(max(ok_times, default=1.0) * 10.0, 1.0)
            self._failed.add(index)
        self._observed[index] = float(step_s)

    def best(self) -> int | None:
        """Best *successful* observation (failures only steer the GP)."""
        ok_obs = {
            i: v for i, v in self._observed.items()
            if i not in self._failed
        }
        if not ok_obs:
            return None
        return min(ok_obs, key=ok_obs.get)


# --------------------------------------------------------------------------
# engine + task loop (reference engine/executor.py task states)
# --------------------------------------------------------------------------


class TaskType:
    ANALYSE = "ANALYSE"
    TUNE = "TUNE"
    DRYRUN = "DRYRUN"
    FINISH = "FINISH"
    FAIL = "FAIL"
    WAIT = "WAIT"


@dataclasses.dataclass
class EngineTask:
    task_type: str
    strategy: Optional[Strategy] = None
    task_id: int = -1


class StrategySearchEngine:
    """Generates candidates, scores them via dry-run, returns the winner.

    Two entry points:
    - :meth:`search` — synchronous, single process (TPU: every host sees
      the same mesh, so one searcher decides for all; the reference needed
      a gRPC task service because strategies rewrote per-rank modules).
    - :meth:`get_task` / :meth:`report_task_result` — the reference-shaped
      task loop for callers that drive the search incrementally.
    """

    def __init__(
        self,
        n_devices: int,
        analysis: ModelAnalysis,
        dry_runner: Optional[DryRunner] = None,
        devices_per_host: int = 4,
        hbm_gb: float = 16.0,
        seq_len: int = 2048,
        max_dryruns: int = 6,
        search_algo: str = "greedy",
        try_low_precision: bool = False,
        loss_parity_tol: float = LOSS_PARITY_TOL,
        **candidate_kwargs,
    ):
        if search_algo not in ("greedy", "bo"):
            raise ValueError(
                f"search_algo must be 'greedy' or 'bo', got {search_algo!r}"
            )
        self._n_devices = n_devices
        self._analysis = analysis
        self._dry_runner = dry_runner
        self._max_dryruns = max_dryruns
        self._algo = search_algo
        self._loss_parity_tol = loss_parity_tol
        self._candidates = candidate_strategies(
            n_devices, analysis, devices_per_host=devices_per_host,
            hbm_gb=hbm_gb, seq_len=seq_len, **candidate_kwargs,
        )
        if try_low_precision:
            # int8 variants of the top candidates: measured selection
            # (reference Fp8Optimization is a production win via
            # TransformerEngine, amp_optimization.py:197; TPU-native
            # equivalent = int8 2x-MXU quantized einsums). An int8
            # candidate may only WIN if its measured loss stays within
            # loss_parity_tol of the same mesh's unquantized run — the
            # gate lives in search()/best_strategy().
            quant = [
                dataclasses.replace(s, compute_dtype="int8")
                for s in self._candidates[:2]
            ]
            self._candidates = (
                self._candidates[:2] + quant + self._candidates[2:]
            )
        self._bo = (
            BayesianSearch(self._candidates) if search_algo == "bo"
            else None
        )
        self._results: list[DryRunResult] = []
        self._cursor = 0
        self._pending: set[int] = set()
        self._finished = False

    @property
    def candidates(self) -> list[Strategy]:
        return list(self._candidates)

    @property
    def results(self) -> list[DryRunResult]:
        return list(self._results)

    # -------------------------------------------------------- synchronous

    def search(self) -> Strategy:
        """Dry-run candidates; fastest feasible step wins.

        ``search_algo="greedy"`` profiles the cost-model top-N in order;
        ``"bo"`` lets the GP/EI loop pick each next dry-run, typically
        reaching the optimum in fewer compiles on large candidate spaces
        (reference bayes_opt_sg.py capability).
        """
        if not self._candidates:
            logger.warning("no feasible candidates; heuristic fallback")
            return auto_strategy(
                self._n_devices, self._analysis.param_count
            )
        if self._dry_runner is None:
            return self._candidates[0]
        if self._algo == "bo":
            for _ in range(min(self._max_dryruns,
                               len(self._candidates))):
                idx = self._bo.suggest()
                if idx is None:
                    break
                r = self._dry_runner.profile(self._candidates[idx])
                self._results.append(r)
                self._bo.observe(idx, r.step_s, r.ok)
        else:
            for s in self._candidates[: self._max_dryruns]:
                self._results.append(self._dry_runner.profile(s))
        ok = [r for r in self._results if r.ok]
        if not ok:
            logger.warning("all dry-runs failed; using top candidate")
            return self._candidates[0]
        best = self._pick_best(ok, verbose=True)
        corr = cost_model_rank_correlation(
            self._candidates, self._results
        )
        if corr is not None:
            logger.info(
                "cost-model calibration: rank correlation with "
                "measured step times = %.2f%s", corr,
                "" if corr >= 0.3 else
                " (weak: analytic ordering unreliable here beyond "
                "memory feasibility)",
            )
        if best.ok:
            logger.info(
                "strategy search: %s wins (%.4fs/step over %d "
                "candidates)", best.strategy.describe(), best.step_s,
                len(ok),
            )
        else:
            logger.warning(
                "strategy search: falling back to unmeasured %s (no "
                "parity-checked candidate succeeded)",
                best.strategy.describe(),
            )
        self._finished = True
        return best.strategy

    # ---------------------------------------------------------- task loop

    def get_task(self) -> EngineTask:
        """Task IDs are candidate indices (both algorithms), so
        ``report_task_result`` can feed the BO observer."""
        if self._finished:
            return EngineTask(TaskType.FINISH, self.best_strategy())
        issued = self._cursor
        if issued >= min(len(self._candidates), self._max_dryruns):
            self._finished = True
            return EngineTask(TaskType.FINISH, self.best_strategy())
        if self._bo is not None:
            idx = self._bo.suggest(exclude=self._pending)
            if idx is None:
                self._finished = True
                return EngineTask(TaskType.FINISH, self.best_strategy())
        else:
            idx = self._cursor
        self._pending.add(idx)
        self._cursor += 1
        return EngineTask(
            TaskType.DRYRUN, self._candidates[idx], task_id=idx
        )

    def report_task_result(self, task_id: int, result: DryRunResult):
        self._results.append(result)
        self._pending.discard(task_id)
        if self._bo is not None and 0 <= task_id < len(self._candidates):
            self._bo.observe(task_id, result.step_s, result.ok)

    def _pick_best(
        self, ok: list["DryRunResult"], verbose: bool = False
    ) -> "DryRunResult":
        """Fastest measured candidate, with the quantization gate: an
        int8/fp8 candidate may only win when its measured loss matches
        the same mesh+remat's unquantized run within loss_parity_tol
        (quantization changes numerics; a fast-but-wrong step must not
        be auto-selected). Gated candidates are skipped, not fatal.
        ``verbose`` logs decisions at info (the one search() call);
        repeated best_strategy()/task-loop calls stay quiet."""

        def is_quant(r):
            return r.strategy.compute_dtype in ("int8", "fp8")

        def sibling(r):
            for o in ok:
                if (
                    not is_quant(o)
                    and o.strategy.mesh == r.strategy.mesh
                    and o.strategy.remat == r.strategy.remat
                ):
                    return o
            return None

        pool = list(ok)
        while pool:
            best = min(pool, key=lambda r: r.step_s)
            if not is_quant(best):
                return best
            sib = sibling(best)
            if (
                sib is not None
                and best.loss is not None
                and sib.loss is not None
                and abs(best.loss - sib.loss)
                <= self._loss_parity_tol * max(abs(sib.loss), 1e-9)
            ):
                if verbose:
                    logger.info(
                        "quantized dtype selected: %s at %.4fs/step "
                        "(unquantized sibling %.4fs, loss %.4f vs %.4f)",
                        best.strategy.compute_dtype, best.step_s,
                        sib.step_s, best.loss, sib.loss,
                    )
                return best
            if verbose:
                logger.info(
                    "quantized candidate %s gated off (no loss-parity "
                    "evidence)", best.strategy.describe(),
                )
            pool = [r for r in pool if r is not best]
        # every measured candidate was a gated-off quantized one (e.g.
        # all unquantized dry-runs OOMed): fall back to the cost-model
        # top UNQUANTIZED candidate rather than silently selecting a
        # strategy the gate just rejected
        for s in self._candidates:
            if s.compute_dtype not in ("int8", "fp8"):
                # search() logs the fallback (it branches on best.ok)
                return DryRunResult(strategy=s, ok=False)
        return min(ok, key=lambda r: r.step_s)

    def best_strategy(self) -> Strategy:
        ok = [r for r in self._results if r.ok]
        if ok:
            return self._pick_best(ok).strategy
        if self._candidates:
            return self._candidates[0]
        return auto_strategy(self._n_devices, self._analysis.param_count)


# --------------------------------------------------------------------------
# convenience: full search over a real model via auto_accelerate
# --------------------------------------------------------------------------


def make_auto_accelerate_dry_runner(
    loss_fn, init_fn, optimizer, param_logical_axes,
    make_batch: Callable[[], object],
    devices=None, seed: int = 0,
) -> DryRunner:
    """DryRunner whose build_fn is a real ``auto_accelerate`` call on the
    user's model with a caller-provided (small) batch factory."""

    def build(strategy: Strategy):
        import jax

        from dlrover_tpu.parallel.accelerate import auto_accelerate

        res = auto_accelerate(
            loss_fn, init_fn, optimizer, param_logical_axes,
            strategy=strategy, devices=devices, seed=seed,
        )
        return res.train_step, res.state, make_batch(), jax.random.key(0)

    return DryRunner(build)


def search_strategy(
    loss_fn, init_fn, optimizer, param_logical_axes, make_batch,
    n_devices: int | None = None, devices=None, seed: int = 0,
    **engine_kwargs,
) -> Strategy:
    """One-call measured search (the reference's search path of
    auto_accelerate, accelerate.py:406 when load_strategy is absent)."""
    import jax

    if n_devices is None:
        n_devices = len(devices) if devices is not None else (
            jax.device_count()
        )
    abstract = jax.eval_shape(init_fn, jax.random.key(seed))
    analysis = analyse_params(abstract)
    runner = make_auto_accelerate_dry_runner(
        loss_fn, init_fn, optimizer, param_logical_axes, make_batch,
        devices=devices, seed=seed,
    )
    engine = StrategySearchEngine(
        n_devices, analysis, dry_runner=runner, **engine_kwargs
    )
    return engine.search()
