"""Tensor/pipeline-parallel serving: one engine spanning a pp×mp mesh
via shard_map.

``ServingEngine(tp=N)`` keeps the engine's central contract — exactly TWO
compiled programs, the ``[max_slots]`` decode step and the
``[max_slots, chunk]`` mixed step — and runs each as ONE ``shard_map``
program over the ``mp`` axis (Megatron-style head/column/row partitioning,
Shoeybi et al. 2019; the 2D inference layouts of Pope et al. 2022 reduce
to this on a 1D mp mesh). ``ServingEngine(pp=P, tp=N)`` adds the second
mesh axis: the stacked decoder layers shard along ``pp`` (embed + the
first ``L/pp`` layers with stage 0, lm_head + the last with stage P-1 —
``models/llama_pipe``'s layout), the KV pool stacks its per-layer pairs
into ONE ``[L, pages, ...]`` pair carved the same way, and each step is
STILL one ``jit(shard_map)`` over the full pp×mp mesh: stage handoff is a
``ppermute`` of the ``[slots, h]`` activation ring inside a ``lax.scan``
over pipeline ticks (:meth:`TPContext.staged_forward`), so
``step_program_counts()`` stays ``{decode: 1, mixed: 1}`` under churn —
no per-stage program zoo. The division of labour:

===========================  =============================================
sharded (per-device)         replicated (host-side / every device)
===========================  =============================================
KV page payloads: the kv-    block tables, seq_lens, content hashes,
head dim of every page       prefix registration, refcounts, eviction —
(`kvh/tp` heads per shard;   ALL pool metadata. Sampling lanes (temps,
int8 scales shard the same   top_ps, seeds, counts). Logits after the
dim)                         final all_gather, so sampling runs once per
q/k/v, gate/up weights       shard on identical values and the
(column-parallel) and        ``fold_in(key, token_index)`` contract is
o/down weights (row-         untouched.
parallel); embed rows and
lm_head columns (vocab)
===========================  =============================================

Attention is fully head-local: the paged scatter, the Pallas paged kernel
and the shared GQA decode core all run per-shard unchanged (the GQA ratio
``h/kvh`` survives sharding because both split by ``tp``). Each
transformer block issues exactly ONE psum (after o_proj / down_proj), the
vocab-parallel embedding one psum, and the vocab-sharded logits one
all_gather — nothing ever gathers the KV pool
(``tests/test_serving_tp.py::TestTPPrograms`` asserts these counts on the
jaxpr).

Because pool arrays and weights stay GLOBAL logical ``jax.Array``s with a
``NamedSharding`` (sharding is a layout property, not a shape change),
every host-side path — spill/restore, snapshot capture, prefix injection,
scrub/rewind/cow — is tp-agnostic: ``device_get`` gathers shards into the
HostTier payload format, so a tp=2 snapshot restores into a tp=1 engine
and vice versa (SERVING.md "Tensor-parallel serving").

CPU verification needs no chip: force a virtual multi-device platform with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
``dryrun_multichip`` harness; tests/conftest.py does this for the whole
suite).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import mesh as mesh_lib
from ..core.mesh import trimmed_spec as _trim
from ..distributed.fleet.mp_layers import manual_mp_region
from ..quantization.serving import QuantizedKV
from .errors import TPConfigError

__all__ = ["TPContext", "validate_tp_config", "partition_devices",
           "collective_counts"]


def validate_tp_config(config, tp: int, pp: int = 1) -> None:
    """Reject un-shardable configs at construction time with a typed
    :class:`TPConfigError` instead of a shape crash inside the compiled
    step. Every dimension the TP layout splits must divide evenly, and
    the decoder stack must carve into ``pp`` equal stages."""
    family = type(config).__name__
    if tp < 1:
        raise TPConfigError(f"tp must be >= 1, got {tp}")
    if pp < 1:
        raise TPConfigError(f"pp must be >= 1, got {pp}")
    if (tp > 1 or pp > 1) and hasattr(config, "cache_layers"):
        raise TPConfigError(
            f"{family}: a model whose layers keep different things "
            f"(cache_layers(): pages, per-slot state, nothing) is served "
            f"at tp=1, pp=1 only; the TP/PP step programs know a uniform "
            f"decoder's shapes (got tp={tp}, pp={pp})")
    if pp > 1:
        layers = getattr(config, "num_hidden_layers", None)
        if layers is not None and layers % pp:
            raise TPConfigError(
                f"{family}: num_hidden_layers={layers} is not divisible "
                f"by pp={pp} (the stacked decoder shards "
                f"{layers // pp or 1}+ layers per stage; stages must be "
                f"equal)")
    if tp == 1:
        return
    checks = (
        ("num_key_value_heads", "KV pool head dim"),
        ("num_attention_heads", "query heads"),
        ("vocab_size", "vocab-parallel embedding / lm_head"),
        ("intermediate_size", "column-parallel gate/up"),
    )
    for field, what in checks:
        val = getattr(config, field, None)
        if val is not None and val % tp:
            raise TPConfigError(
                f"{family}: {field}={val} is not divisible by tp={tp} "
                f"({what} shards this dimension)")


def partition_devices(n_groups: int, pp: int, tp: int | None = None,
                      devices=None) -> list[list]:
    """Carve the device list into ``n_groups`` disjoint parallel groups
    — a fleet replica IS a pp×tp group. Two calling forms:

    - ``partition_devices(n, tp)`` (2 positional args, the original
      TP-only form): ``n`` groups of ``tp`` devices each;
    - ``partition_devices(n, pp, tp)``: ``n`` groups of ``pp * tp``
      devices each, every slice feeding
      ``ServingEngine(pp=pp, tp=tp, tp_devices=slice)`` (the TPContext
      folds the flat slice into its pp×mp mesh, pp-major).

    Groups are contiguous disjoint slices; asking for more devices than
    exist raises a typed :class:`TPConfigError` naming the XLA flag that
    fakes them on CPU."""
    if tp is None:
        pp, tp = 1, pp
    if pp < 1 or tp < 1:
        raise TPConfigError(f"pp and tp must be >= 1, got pp={pp} tp={tp}")
    devs = list(devices) if devices is not None else list(jax.devices())
    group = pp * tp
    need = n_groups * group
    if len(devs) < need:
        raise TPConfigError(
            f"{n_groups} groups of pp={pp} x tp={tp} need {need} devices, "
            f"have {len(devs)} (CPU: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})")
    return [devs[i * group:(i + 1) * group] for i in range(n_groups)]


def _stack_entry(arr, j):
    """Slice layer ``j`` out of a stacked pool array (QuantizedKV slices
    codes AND scales — the pair travels together, same as _page_copy)."""
    if isinstance(arr, QuantizedKV):
        return QuantizedKV(arr.q[j], arr.scale[j])
    return arr[j]


def _stack_update(arr, j, new):
    """Write layer ``j``'s updated pool back into the stacked array."""
    if isinstance(arr, QuantizedKV):
        return QuantizedKV(arr.q.at[j].set(new.q),
                           arr.scale.at[j].set(new.scale))
    return arr.at[j].set(new)


class TPContext:
    """Everything the engine needs to span a pp×tp group: the mesh over
    its device slice, the weight/pool shardings, and the shard_map
    wrapper that turns a step body into ONE manual-mp program. At
    ``pp=1`` this is exactly the original TP context (1-D mp mesh);
    ``pp>1`` adds the leading pipeline axis, stacks the decoder-layer
    state along it, and provides :meth:`staged_forward` — the in-program
    ppermute ring the pp step bodies are built from."""

    axis = "mp"
    pp_axis = "pp"

    #: staged-state key marker: ``model.layers.*.self_attn.q_proj.weight``
    #: names the [L, ...] stack of every layer's ``q_proj.weight``
    STACK = "*"

    def __init__(self, model, tp: int, devices=None, pp: int = 1):
        validate_tp_config(model.config, tp, pp)
        self.tp = int(tp)
        self.pp = int(pp)
        need = self.tp * self.pp
        devs = list(devices) if devices is not None else list(jax.devices())
        if len(devs) < need:
            raise TPConfigError(
                f"pp={pp} x tp={tp} needs {need} devices, have {len(devs)} "
                f"(CPU: set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need})")
        if self.pp > 1:
            # pp-major device folding: stage i gets devs[i*tp:(i+1)*tp],
            # so a partition_devices slice maps stages contiguously
            self.mesh = mesh_lib.make_mesh(
                {self.pp_axis: self.pp, self.axis: self.tp},
                devices=devs[:need])
        else:
            self.mesh = mesh_lib.make_mesh({self.axis: tp},
                                           devices=devs[:tp])
        self.devices = devs[:need]
        # weight specs from the model's creation-time PartitionSpecs: keep
        # the mp entries, null every other axis (the serving mesh has only
        # mp beside pp); state keys absent from spec_dict (buffers) are
        # replicated
        self._specs = {}
        for name, spec in model.spec_dict().items():
            if spec is None:
                self._specs[name] = P()
            else:
                self._specs[name] = _trim(*[a if a == self.axis else None
                                            for a in spec])
        if self.pp > 1:
            self._init_pp(model)

    def _init_pp(self, model) -> None:
        """Pipeline-stage metadata from the model's ``pp_parts``
        decomposition: the stacked-layer key prefix, a template layer
        whose functional_call consumes one stacked slice, and the
        embed/head closures that reproduce the model's forward bitwise
        from a staged state dict."""
        parts = getattr(model, "pp_parts", None)
        if parts is None:
            raise TPConfigError(
                f"pp={self.pp} needs a model exposing pp_parts() "
                f"(the embed/layers/head decomposition); "
                f"{type(model).__name__} does not")
        parts = parts()
        self._pp_prefix = parts["layer_prefix"]
        self._pp_layers = int(parts["num_layers"])
        self._pp_template = parts["template"]
        self._pp_embed = parts["embed"]
        self._pp_head = parts["head"]
        self._pp_rope = tuple(parts["rope_keys"])
        # stacked-state specs: layer 0's mp spec with the pp axis
        # prepended on the new leading (layer) dim
        pre0 = f"{self._pp_prefix}0."
        self._pp_rel_keys = []
        for name in list(self._specs):
            if name.startswith(pre0):
                rel = name[len(pre0):]
                self._pp_rel_keys.append(rel)
                self._specs[self._stack_key(rel)] = _trim(
                    self.pp_axis, *self._specs[name])

    def _stack_key(self, rel: str) -> str:
        return f"{self._pp_prefix}{self.STACK}.{rel}"

    def stage_state(self, state: dict) -> dict:
        """Convert a flat model state dict into the staged pp layout:
        every per-layer key ``model.layers.<i>.<rel>`` folds into ONE
        stacked ``model.layers.*.<rel>`` array of shape ``[L, ...]``
        (sharded ``P('pp', ...)`` — stage s holds layers
        ``[s*L/pp, (s+1)*L/pp)``, llama_pipe's contiguous-stage layout);
        everything else (embed, final norm, lm_head, rope caches) keeps
        its key and replicates across pp."""
        staged: dict = {}
        layers: dict[str, dict[int, object]] = {}
        pre = self._pp_prefix
        for k, v in state.items():
            if k.startswith(pre):
                idx, rel = k[len(pre):].split(".", 1)
                layers.setdefault(rel, {})[int(idx)] = v
            else:
                staged[k] = v
        for rel, by_idx in layers.items():
            staged[self._stack_key(rel)] = jnp.stack(
                [by_idx[i] for i in range(self._pp_layers)])
        return staged

    # -- shardings ---------------------------------------------------------

    def spec_for(self, name: str) -> P:
        return self._specs.get(name, P())

    def shard_state(self, state: dict) -> dict:
        """One-time placement of the weights/buffers onto the mesh
        (column/row/vocab layout per the creation-time specs; stacked
        layer keys additionally split their leading layer dim on pp).
        A pp>1 engine stages the state first (:meth:`stage_state`)."""
        return {k: jax.device_put(v, NamedSharding(self.mesh, self.spec_for(k)))
                for k, v in state.items()}

    def kv_shardings(self):
        """(payload, scale) NamedShardings for pool arrays: pages and
        rows replicated, the kv-head dim split on mp — each shard owns
        ``kvh/tp`` heads of EVERY page, so all page metadata stays valid
        on every shard. At pp>1 the pool is ONE stacked
        ``[L, pages, ...]`` pair and the leading layer dim splits on pp
        — each stage's pool holds only its own layers' pages, so HBM
        per chip drops ~1/pp."""
        if self.pp > 1:
            spec = self._pp_pool_spec()
            return (NamedSharding(self.mesh, spec),
                    NamedSharding(self.mesh, spec))
        return (NamedSharding(self.mesh, _trim(None, None, self.axis, None)),
                NamedSharding(self.mesh, P(None, None, self.axis)))

    def _pp_pool_spec(self) -> P:
        """Canonical spec of the stacked pool. A size-1 mp axis (pp>1
        with tp=1) is dropped along with trailing Nones — jax
        canonicalizes output shardings exactly this way, and the device
        placement must match so the pool arrays a step program RETURNS
        hash to the same jit cache key as the ones a restore device_puts
        (else the first post-restore decode would retrace)."""
        return _trim(self.pp_axis, None, None,
                     self.axis if self.tp > 1 else None)

    def _kv_entry(self, arr):
        if self.pp > 1:
            spec = self._pp_pool_spec()
            if hasattr(arr, "q"):
                return type(arr)(spec, spec)
            return spec
        if hasattr(arr, "q"):  # QuantizedKV: codes + per-(row, head) scales
            return type(arr)(_trim(None, None, self.axis, None),
                             P(None, None, self.axis))
        return _trim(None, None, self.axis, None)

    def pool_specs(self, pools):
        return [(self._kv_entry(pk), self._kv_entry(pv)) for pk, pv in pools]

    # -- the staged (pipeline) forward ------------------------------------

    def staged_forward(self, state, pools, toks, tables, seq_lens, active,
                       n_live, waves: int = 1):
        """The pp step bodies' forward: embed the full ``[S, K]`` chunk,
        ring the activations through the staged decoder, return
        replicated ``[S, K, V]`` logits plus the updated stacked pool.
        Runs INSIDE the one shard_map body (manual-mp region active), so
        the whole pipeline — fill, drain, every wave — is a single
        compiled program no matter how requests churn.

        The ring is ``models/llama_pipe``'s GPipe schedule on the wave
        axis: the chunk splits into ``waves`` microbatches of
        ``Kw = K // waves`` rows, and a ``lax.scan`` over
        ``T = waves + pp - 1`` ticks runs wave ``w = t - r`` on stage
        ``r`` (validity-masked with ``jnp.where`` — never ``lax.cond``,
        collectives must run in SPMD lockstep), handing each tick's
        activations to stage ``r+1`` with ONE ``lax.ppermute``. Stage 0
        injects the wave's embedded rows; stage pp-1 banks its outputs.
        With ``waves == 1`` the schedule degrades to the naive
        sequential pipeline (1 busy stage per tick — the (pp-1)/pp
        bubble); ``waves == pp`` overlaps stages so the bubble shrinks
        to (pp-1)/(2pp-1).

        Masking keeps the math bitwise equal to the unstaged engine:
        invalid ticks run with ``active=False`` so every pool write
        lands on scratch page 0, per-wave lanes shift by the wave's row
        offset (``seq_lens + w*Kw``, ``clip(n_live - w*Kw, 0, Kw)``) so
        each row sees exactly the positions the full-chunk program gives
        it, and the final cross-stage broadcast is a psum of the
        last-stage outputs against zeros. Sampling runs AFTER the
        final-stage logits gather, replicated on every device — the
        ``fold_in(key, token_index)`` contract never sees the mesh."""
        from ..nn.module import functional_call
        pp = self.pp
        (pk, pv), = pools
        S, K = toks.shape
        W = int(waves)
        Kw = K // W
        emb = self._pp_embed(state, toks)              # [S, K, H]; 1 mp psum
        r = jax.lax.axis_index(self.pp_axis)
        is_first = r == 0
        is_last = r == pp - 1
        perm = [(i, (i + 1) % pp) for i in range(pp)]
        n_local = self._pp_layers // pp
        template = self._pp_template
        sliced = {rel: state[self._stack_key(rel)]
                  for rel in self._pp_rel_keys}

        @jax.named_scope("pp_tick")
        def tick(carry, t):
            # one scope for every tick: the waves run inside this scanned
            # body, so the device trace has their operations under
            # ``pp_tick/...`` (the host cannot see a wave start or end)
            h, pk, pv, outs = carry
            w = t - r
            valid = (w >= 0) & (w < W)
            wc = jnp.clip(w, 0, W - 1)
            # stage 0 sources the wave from the embedded chunk; every
            # other stage consumes the ring input its predecessor
            # ppermuted at the end of the previous tick
            src = jax.lax.dynamic_slice_in_dim(emb, wc * Kw, Kw, axis=1)
            h = jnp.where(is_first, src, h)
            act_w = active & valid
            paged = (tables, seq_lens + wc * Kw, act_w)
            if n_live is not None:
                paged = paged + (jnp.clip(n_live - wc * Kw, 0, Kw),)
            for j in range(n_local):
                cache = (_stack_entry(pk, j), _stack_entry(pv, j))
                (h, (nk, nv)), _ = functional_call(
                    template, {rel: arr[j] for rel, arr in sliced.items()},
                    h, state[self._pp_rope[0]], state[self._pp_rope[1]],
                    None, cache, 0, paged, training=False)
                pk = _stack_update(pk, j, nk)
                pv = _stack_update(pv, j, nv)
            outs_new = jax.lax.dynamic_update_slice_in_dim(
                outs, h, wc * Kw, axis=1)
            outs = jnp.where(is_last & valid, outs_new, outs)
            h = jax.lax.ppermute(h, self.pp_axis, perm)
            return (h, pk, pv, outs), None

        carry0 = (jnp.zeros((S, Kw, emb.shape[-1]), emb.dtype), pk, pv,
                  jnp.zeros_like(emb))
        (h, pk, pv, outs), _ = jax.lax.scan(
            tick, carry0, jnp.arange(W + pp - 1))
        # ring close: broadcast the last stage's banked hidden states to
        # every stage (everyone else contributes exact zeros), then run
        # the replicated head — norm + lm_head + the one mp logits
        # gather — identically everywhere
        hidden = jax.lax.psum(
            jnp.where(is_last, outs, jnp.zeros_like(outs)), self.pp_axis)
        logits = self._pp_head(state, hidden)
        return logits, [(pk, pv)]

    # -- step compilation --------------------------------------------------

    def compile_step(self, fn, state, pools, n_lanes: int, n_lead: int):
        """Wrap a step body ``fn(state, pools, *lanes) -> (*outs, pools)``
        into ONE jitted shard_map program over the mp axis.

        All host-built lanes (tokens, block tables, seq_lens, sampling
        params) go in replicated; the ``n_lead`` leading outputs (sampled
        tokens, finite masks, …) come out replicated — they are computed
        identically on every shard from the all-gathered logits, which is
        what keeps sampling and the fold_in contract single-program.
        ``check_vma=False`` skips the replication proof for exactly those
        outputs. The pools are donated, as in the single-chip bodies:
        each shard writes into the arrays it was given. The un-jitted
        shard_map callable is kept on the returned function as
        ``_tp_inner`` so the collective-count report
        (:func:`collective_counts`) can trace it."""
        ax = self.axis

        def body(state, pools, *lanes):
            with manual_mp_region(ax):
                return fn(state, pools, *lanes)

        in_specs = ({k: self.spec_for(k) for k in state},
                    self.pool_specs(pools), *([P()] * n_lanes))
        out_specs = (*([P()] * n_lead), self.pool_specs(pools))
        inner = shard_map(body, mesh=self.mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
        step = jax.jit(inner, donate_argnums=(1,))
        step._tp_inner = inner
        return step


# -- collective-count report ----------------------------------------------

_COLLECTIVES = ("psum", "all_gather", "all_to_all", "all_reduce",
                "reduce_scatter", "ppermute")


def _subjaxprs(v):
    if hasattr(v, "eqns"):          # Jaxpr
        yield v
    elif hasattr(v, "jaxpr"):       # ClosedJaxpr
        yield v.jaxpr
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _subjaxprs(item)


def collective_counts(fn, *args) -> dict[str, int]:
    """Trace ``fn(*args)`` and count collective primitives, recursing into
    sub-jaxprs (shard_map/pjit/scan bodies). The TP contract audited by
    ``tests/test_serving_tp.py``: a step program carries exactly
    ``2 * num_layers + 1`` psums (one per attention block, one per MLP
    block, one for the vocab-parallel embedding) and exactly 1 all_gather
    (the vocab-sharded logits) — never an all_gather of the KV pool.

    Beside the plain per-primitive STATIC counts (``psum``, ``ppermute``,
    … — occurrences in the traced program, the original report), the dict
    carries two derived families the pp audit
    (``tests/test_serving_pp.py``) pins:

    - ``"<prim>[<axis>]"`` — static count split by mesh axis, so the TP
      budget and the pipeline ring are separable: a pp×mp step shows
      ``psum[mp] == 2*L/pp + 1`` (each stage's layer blocks + the
      vocab-parallel embed) and ``psum[pp] == 1`` (the ring-close
      broadcast of the last stage's hidden states).
    - ``"<prim>_trips"`` / ``"<prim>_trips[<axis>]"`` — TRIP counts:
      static counts weighted by the ``lax.scan`` trip count(s) enclosing
      the primitive, i.e. how many times the collective actually runs
      per step. The one ppermute inside the pipeline scan is static 1
      but ``ppermute_trips[pp] == waves + pp - 1`` — exactly ``pp`` ring
      hops for the unwaved decode step (waves=1).
    """
    jaxpr = jax.make_jaxpr(fn)(*args)
    counts: dict[str, int] = {}

    def _axes(eqn):
        ax = eqn.params.get("axes")
        if ax is None:
            ax = eqn.params.get("axis_name")
        if ax is None:
            return ()
        if isinstance(ax, (tuple, list)):
            return tuple(str(a) for a in ax)
        return (str(ax),)

    def walk(jx, trips):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            for c in _COLLECTIVES:
                if name == c or name.startswith(c + "_") or name == c + "2":
                    counts[c] = counts.get(c, 0) + 1
                    tk = f"{c}_trips"
                    counts[tk] = counts.get(tk, 0) + trips
                    for a in _axes(eqn):
                        ak, atk = f"{c}[{a}]", f"{c}_trips[{a}]"
                        counts[ak] = counts.get(ak, 0) + 1
                        counts[atk] = counts.get(atk, 0) + trips
                    break
            inner = trips
            if name == "scan":
                inner = trips * int(eqn.params.get("length", 1))
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    walk(sub, inner)

    walk(jaxpr.jaxpr, 1)
    return counts
