"""Partition-rule registry: persistable var names -> PartitionSpecs.

The GSPMD serving analog of fmengine's ``match_partition_rules`` (and of
the sharding-rule lists `parallel/sharding.py` already feeds the
training-side DistributedExecutor): an ordered (regex, PartitionSpec)
table, FIRST match wins, resolved per var name so a whole model family
— attention qkv/o projections, FFN/SwiGLU weights, embeddings, AND the
serving slot-pool's ``<family>_{k,v}cache_*`` persistables — picks up
tensor-parallel placements with zero per-model edits (the same
no-model-edits discipline as the PR 11 fuse passes).

Differences from ``sharding.ShardingRules`` (kept for the training
paths) that the SERVING pool needs:

- **per-model-family rule tables** (``register_partition_rules`` /
  ``partition_rules_for``): the engine resolves the table from the
  model config's ``partition_family``, so a bert-family pool and a
  gpt2-family pool shard correctly side by side;
- **replicate-by-default that LOGS**: every name that falls through to
  replication is recorded (``replicated_log``) and logged once — a
  silently-replicated KV pool is the failure mode this registry exists
  to make visible;
- **an SPMD lowering context** (``spmd_lowering``/``current_spmd``)
  the op lowerings consult, so ``fused_attention``'s vector-QStart
  branch and ``slot_cache_write`` can wrap their kernels in
  ``shard_map`` / sharding constraints only when a mesh is live.
"""

import logging
import re
import threading
from contextlib import contextmanager

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "PartitionRules", "TrainPartitionRules", "StageResolution",
    "register_partition_rules",
    "partition_rules_for", "train_partition_rules_for",
    "registered_families", "annotate_spmd", "spmd_lowering",
    "current_spmd", "P",
]

log = logging.getLogger("paddle_tpu.parallel.partition_rules")


class PartitionRules:
    """Ordered (regex, PartitionSpec) table; ``spec_for`` resolves a var
    name (first match wins) with three guards, each of which REPLICATES
    and records why instead of failing:

    - scalar guard: 0-d / 1-element values never shard (SNIPPETS [3]'s
      ``len(leaf.shape) == 0 or prod == 1`` rule);
    - rank guard: a spec with more named axes than the value has dims
      replicates (optimizer counters sharing a param's name prefix);
    - divisibility guard (``sharding_for``, mesh-aware): a dim that
      does not divide by its axis size replicates — a 3-kv-head cache
      on a 2-way mesh must not half-shard.

    ``sharding_for`` says how a persistable is STORED (a jax.Array
    argument needs even shards).  ``compute_spec_for`` says how a value
    of that name is COMPUTED inside the step: the same rule with the
    divisibility guard lifted, since a sharding constraint admits uneven
    shards.  Where the two differ the name is recorded in ``uneven_log``
    as (name, dim, axis).

    Unmatched names fall through to REPLICATED and are logged once per
    name — the registry's contract is that nothing shards silently and
    nothing replicates invisibly."""

    def __init__(self, rules=None, mp_axis="mp"):
        self.mp_axis = mp_axis
        self.rules = [(pat, re.compile(pat), spec)
                      for pat, spec in (rules or [])]
        # (name, reason) for every replicate-fallback decision, in
        # resolution order; dedup'd so steady-state re-resolution of the
        # same scope names does not grow it unboundedly
        self.replicated_log = []
        self._logged = set()
        # (name, dim, axis) for every name whose stored sharding (the
        # divisibility guard's) differs from the spec it is computed in
        # (compute_spec_for), once per name
        self.uneven_log = []

    def add(self, pattern, spec):
        self.rules.append((pattern, re.compile(pattern), spec))
        return self

    def match(self, name):
        """(spec, pattern) of the FIRST rule matching `name`;
        (None, None) when no rule matches."""
        for pat, cre, spec in self.rules:
            if cre.search(name):
                return spec, pat
        return None, None

    def _fallback(self, name, reason):
        if name not in self._logged:
            self._logged.add(name)
            self.replicated_log.append((name, reason))
            log.info("partition_rules: replicating %r (%s)", name, reason)
        return P()

    def spec_for(self, name, shape=None):
        if shape is not None and (
                len(shape) == 0 or int(np.prod(shape)) <= 1):
            # scalar guard — never worth logging (counters, beta_pows)
            return P()
        spec, pat = self.match(name)
        if spec is None:
            return self._fallback(name, "no rule matched")
        if shape is not None and len(spec) > len(shape):
            return self._fallback(
                name, "rank %d < rule %r spec %s" % (len(shape), pat,
                                                     spec))
        return spec

    def sharding_for(self, mesh, name, shape=None):
        """NamedSharding for `name` under `mesh`, applying the
        divisibility guard on top of ``spec_for``."""
        spec = self.spec_for(name, shape)
        if shape is not None and len(spec) > 0:
            from .mesh import mesh_axis_sizes

            sizes = mesh_axis_sizes(mesh)
            for dim, axes in zip(shape, tuple(spec)):
                if axes is None:
                    continue
                for ax in (axes if isinstance(axes, tuple) else (axes,)):
                    if int(dim) % int(sizes.get(ax, 1)) != 0:
                        return NamedSharding(mesh, self._fallback(
                            name, "dim %d !%% %s=%d"
                            % (dim, ax, sizes.get(ax, 1))))
        return NamedSharding(mesh, spec)

    def compute_spec_for(self, mesh, name, shape):
        """The rule's own PartitionSpec for a VALUE of `name` and `shape`
        inside a step traced under `mesh`: ``spec_for``'s scalar and rank
        guards stay, an axis the mesh lacks (or holds at size 1) drops
        out, and the divisibility guard is lifted — a dim at least as
        long as its axis is computed in shards whether or not the axis
        divides it (GPT-2's 50257-row table over mp=2: 25129 rows a
        rank, the last shard one row short).  The same placement as the
        stored spec wherever every axis divides."""
        from .mesh import mesh_axis_sizes

        spec = self.spec_for(name, shape)
        sizes = mesh_axis_sizes(mesh)
        entries = []
        for dim, axes in zip(shape, tuple(spec)):
            axes = axes if isinstance(axes, tuple) else (axes,)
            live = tuple(ax for ax in axes
                         if ax is not None and int(sizes.get(ax, 1)) > 1)
            n = int(np.prod([sizes[ax] for ax in live])) if live else 1
            if not live or int(dim) < n:
                entries.append(None)
                continue
            if int(dim) % n and not any(name == e[0]
                                        for e in self.uneven_log):
                self.uneven_log.append((name, int(dim), "+".join(live)))
                log.info("partition_rules: %r stored replicated, computed "
                         "in uneven shards (dim %d over %s=%d)",
                         name, dim, "+".join(live), n)
            entries.append(live[0] if len(live) == 1 else live)
        return P(*entries)

    def match_table(self, named_shapes):
        """Resolve a whole {name: shape} table at once.  Returns
        (specs dict, replicated list) where `replicated` carries the
        (name, reason) fallbacks from THIS resolution — what the bench
        and the engine surface as 'these stayed replicated'."""
        before = len(self.replicated_log)
        specs = {n: self.spec_for(n, s) for n, s in named_shapes.items()}
        return specs, self.replicated_log[before:]


# ---------------------------------------------------------------------------
# training derived names: grads and optimizer state follow their param
# ---------------------------------------------------------------------------
# <param>@GRAD — the backward.py convention the PR 13 verifier models
_GRAD_SUFFIX = re.compile(r"@GRAD(?:@RENAME@.*)?$")
# <param>_<kind>_<n> — the exact Optimizer._add_accumulator kinds (the
# same list parallel/sharding.py's zero1_rules keys on); *_pow_acc
# scalars are deliberately absent (the scalar guard replicates them)
_ACC_SUFFIX = re.compile(
    r"_(moment[12]?|momentum|velocity|inf_norm|_avg_squared_grad|"
    r"_avg_squared_update|mean_square|mean_grad|squared|linear)"
    r"(_\d+)?$")
# bf16 AMP cast intermediates mirror <var>@RAW_BF16; master params keep
# the param's own name (and therefore its spec) — nothing to strip there
_CAST_SUFFIX = re.compile(r"@RAW_BF16$")


class TrainPartitionRules(PartitionRules):
    """The training extension of the serving rule table: ONE table
    covers params AND every name training derives from them —

    - ``<param>@GRAD`` shards like its param (the partial-sum
      all-reduce the SPMD partitioner emits is the PR 6 allreduce-mean
      on the dp axis of the same mesh);
    - optimizer accumulators ``<param>_<kind>_<n>`` shard like their
      param — ZeRO-style sharded optimizer state as a registry pass
      (``beta*_pow_acc`` [1]-scalars hit the scalar guard and
      replicate, unlogged);
    - bf16 AMP cast mirrors ``<var>@RAW_BF16`` follow the base var;
      f32 master params carry the param's own name, so they keep its
      spec with no extra rule.

    ``dp_axis`` names the data-parallel mesh axis the executor shards
    feed batches over (replicated when absent from the mesh)."""

    def __init__(self, rules=None, mp_axis="mp", dp_axis="dp"):
        super(TrainPartitionRules, self).__init__(rules, mp_axis=mp_axis)
        self.dp_axis = dp_axis

    @staticmethod
    def base_name(name):
        """Strip the derived-name suffixes down to the param name:
        grad first (a grad of a cast is <x>@RAW_BF16@GRAD), then the
        cast mirror, then ONE accumulator suffix."""
        name = _GRAD_SUFFIX.sub("", name)
        name = _CAST_SUFFIX.sub("", name)
        return _ACC_SUFFIX.sub("", name)

    def match(self, name):
        return super(TrainPartitionRules, self).match(self.base_name(name))

    def stage_resolution(self, stage_of_param, n_stages):
        """Stage-scoped resolution for pipeline parallelism: lift this
        table's derived-name discipline (grads / Adam moments / bf16 cast
        mirrors resolve through their param) to stage ownership, so the
        WHOLE optimizer-state family of a param lands on that param's
        pipeline stage.  `stage_of_param` maps raw param names to stage
        ids in [0, n_stages)."""
        return StageResolution(stage_of_param, n_stages)


# Adam's beta-power accumulators are deliberately absent from _ACC_SUFFIX
# (the scalar guard replicates them for GSPMD sharding, so stripping was
# never needed) — stage ownership DOES need them to follow their param.
_POW_SUFFIX = re.compile(r"_beta[12]_pow_acc(_\d+)?$")
# backward.py's un-merged grad contributions (`<p>@GRAD_0`) feed the
# optimizer directly when a param has a single contribution; stage
# ownership must resolve those too, where GSPMD never sees them (grads
# are internal activations there, not placed state)
_GRAD_N_SUFFIX = re.compile(r"@GRAD(_\d+)?(@RENAME@.*)?$")


class StageResolution:
    """Maps params and every training-derived name (grads, Adam moments,
    beta-pow accumulators, bf16 cast mirrors) to a pipeline stage id.
    Names whose base resolves to no known param return None — callers
    treat those as shared/replicated state (learning rate, counters)."""

    def __init__(self, stage_of_param, n_stages):
        self.stage_of_param = dict(stage_of_param)
        self.n_stages = int(n_stages)

    def base_name(self, name):
        name = _GRAD_N_SUFFIX.sub("", name)
        name = _CAST_SUFFIX.sub("", name)
        name = _POW_SUFFIX.sub("", name)
        return _ACC_SUFFIX.sub("", name)

    def stage_for(self, name):
        if name in self.stage_of_param:
            return self.stage_of_param[name]
        return self.stage_of_param.get(self.base_name(name))

    def names_by_stage(self, names):
        """Partition `names` into ([stage0_names, ...], shared_names),
        preserving input order within each bucket."""
        staged = [[] for _ in range(self.n_stages)]
        shared = []
        for n in names:
            s = self.stage_for(n)
            (shared if s is None else staged[s]).append(n)
        return staged, shared


def train_partition_rules_for(family, mp_axis="mp", dp_axis="dp"):
    """The registered family table lifted to TRAINING resolution: the
    same rule list as ``partition_rules_for`` wrapped so grads and
    optimizer state resolve through their param's rule."""
    base = partition_rules_for(family, mp_axis)
    tr = TrainPartitionRules(mp_axis=base.mp_axis, dp_axis=dp_axis)
    tr.rules = list(base.rules)
    return tr


# ---------------------------------------------------------------------------
# per-model-family rule tables
# ---------------------------------------------------------------------------
_FAMILIES = {}


def register_partition_rules(family, factory):
    """Register `factory(mp_axis) -> PartitionRules` for a model family
    (the name models expose as ``Config.partition_family``)."""
    _FAMILIES[family] = factory
    return factory


def registered_families():
    return sorted(_FAMILIES)


def partition_rules_for(family, mp_axis="mp"):
    """The registered rule table for `family`, bound to `mp_axis`."""
    if family not in _FAMILIES:
        raise KeyError(
            "no partition rules registered for model family %r "
            "(known: %s)" % (family, ", ".join(registered_families())))
    return _FAMILIES[family](mp_axis)


def _decoder_rules(mp):
    """The shared decoder-block patterns (transformer.py's param naming,
    reused verbatim by gpt2/bert builders): qkv & ffn-in column-parallel,
    attn-out & ffn-out row-parallel, KV slot-pool on the HEADS axis."""
    return [
        # the learned position table is gathered per position — keep it
        # replicated, and keep this rule BEFORE the emb.w vocab rule
        # (re.search would otherwise match 'emb.w' inside 'pos_emb.w')
        (r"pos_emb\.w", P()),
        (r"mha_[qkv]\.w", P(None, mp)),
        (r"mha_o\.w", P(mp, None)),
        (r"ffn_(in|gate|up)\.w", P(None, mp)),
        (r"ffn_in\.b", P(mp)),
        (r"ffn_out\.w", P(mp, None)),
        # the token embedding over the vocabulary, like the untied
        # softmax_out.w below.  STORED in shards where mp divides the
        # vocabulary: lookup and the tied logits matmul (x @ emb.w^T)
        # then run on the rank's rows and emit vocab-sharded logits.
        # Where it does not (GPT-2's 50257 rows over mp=2) the
        # divisibility guard stores the table, its moments and its bf16
        # cast replicated; a TRAINING step still computes it in shards
        # (compute_spec_for: 25129 rows a rank, the gradient reduced over
        # dp in halves and gathered once on its way to the optimizer),
        # a serving step reads it whole, as stored
        (r"emb\.w", P(mp, None)),
        (r"softmax_out\.w", P(None, mp)),
        # the serving slot-pool persistables [B, n_kv, T_max, Dh]:
        # HEADS axis — per-head attention is embarrassingly parallel,
        # so pool bytes/device drop 1/N with zero cross-slot traffic
        (r"_(k|v)cache_\d+$", P(None, mp, None, None)),
    ]


register_partition_rules(
    "gpt2", lambda mp: PartitionRules(_decoder_rules(mp), mp_axis=mp))
register_partition_rules(
    "transformer", lambda mp: PartitionRules(_decoder_rules(mp),
                                             mp_axis=mp))
register_partition_rules(
    "bert", lambda mp: PartitionRules(_decoder_rules(mp), mp_axis=mp))


# ---------------------------------------------------------------------------
# program stamping + the SPMD lowering context
# ---------------------------------------------------------------------------
def annotate_spmd(program, mesh, rules):
    """Stamp `program` for the executor's GSPMD path: persistables
    place per `rules`, the traced step jits with those in/out shardings,
    and the op lowerings see ``current_spmd()`` while tracing.  The
    stamp changes EXECUTION placement only — the program IR is
    untouched (tools/check_program.py verifies the stamped program
    identically to the plain one)."""
    program._spmd = {"mesh": mesh, "rules": rules}
    return program


_SPMD_STATE = threading.local()


@contextmanager
def spmd_lowering(mesh, rules):
    """Bind (mesh, rules) around a trace so op lowerings can emit
    shard_map-wrapped kernels / sharding constraints.  The executor's
    _run_spmd path is the only caller; nesting restores the previous
    binding (a solo-device trace inside a mesh step sees None)."""
    prev = getattr(_SPMD_STATE, "ctx", None)
    _SPMD_STATE.ctx = (mesh, rules)
    try:
        yield
    finally:
        _SPMD_STATE.ctx = prev


def current_spmd():
    """(mesh, rules) when tracing under spmd_lowering, else None."""
    return getattr(_SPMD_STATE, "ctx", None)
