"""Serving metric families — the one owner of their names/labels.

The serving engine, the HTTP front-end, and the bench all record through
this bundle so the families can never be declared twice with diverging
label sets (the registry raises on that).  Names continue the PR-1 set
(``dl4j_serving_requests_total`` etc.) and add the engine-era families:
bucket utilization (how much of each dispatched tile was real rows),
shed counter by reason (queue_full / deadline / shutdown), model swap
counter, and AOT warmup timings.
"""

from __future__ import annotations

import itertools

from deeplearning4j_tpu.observability.metrics import get_registry

_ENGINE_IDS = itertools.count()

_ROW_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
_UTIL_BUCKETS = (0.0625, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)


class ServingMetrics:
    """All serving families, plus this engine's per-instance gauge
    children (labeled ``server=`` with a process-unique id so a second
    engine neither clobbers nor zeroes the first's gauges)."""

    def __init__(self, registry=None, server_id: str = None):
        reg = registry if registry is not None else get_registry()
        self.registry = reg
        self.server_id = (server_id if server_id is not None
                          else f"s{next(_ENGINE_IDS)}")
        self.requests = reg.counter(
            "dl4j_serving_requests_total",
            "Predict requests by outcome", labels=("status",))
        self.latency = reg.histogram(
            "dl4j_serving_request_seconds",
            "End-to-end predict latency (enqueue -> response ready, "
            "including micro-batching wait)")
        self.queue_wait = reg.histogram(
            "dl4j_serving_queue_wait_seconds",
            "Time a request spent queued before its batch dispatched")
        self.request_rows = reg.histogram(
            "dl4j_serving_request_rows",
            "Rows per predict request", buckets=_ROW_BUCKETS)
        self.batch_rows = reg.histogram(
            "dl4j_serving_batch_rows",
            "Rows per dispatched micro-batch (padding excluded)",
            buckets=_ROW_BUCKETS)
        self.bucket_util = reg.histogram(
            "dl4j_serving_bucket_utilization",
            "Real rows / bucket rows per dispatched forward pass (1.0 = "
            "no padding FLOPs wasted)", buckets=_UTIL_BUCKETS)
        self.shed = reg.counter(
            "dl4j_serving_shed_total",
            "Requests shed by admission control, by reason",
            labels=("reason",))
        self.swaps = reg.counter(
            "dl4j_serving_model_swaps_total",
            "Completed model hot-swaps", labels=("model",))
        self.warmup_seconds = reg.histogram(
            "dl4j_serving_warmup_seconds",
            "Wall time of one model version's AOT bucket warmup")
        self.warmup_shapes = reg.gauge(
            "dl4j_serving_warmup_shapes",
            "Bucket shapes precompiled for the active version",
            labels=("model",))
        # per-instance children
        self.queue_depth = reg.gauge(
            "dl4j_serving_queue_depth",
            "Requests waiting for the micro-batch dispatcher",
            labels=("server",)).labels(server=self.server_id)
        self._max_batch_fam = reg.gauge(
            "dl4j_serving_max_batch",
            "Configured micro-batch row budget", labels=("server",))

    def set_max_batch(self, max_batch: int) -> None:
        self._max_batch_fam.set(max_batch, server=self.server_id)

    def bind_queue_depth(self, fn) -> None:
        """Live queue-depth gauge (the caller passes a weakref-safe
        callable so the registry never pins the engine)."""
        self.queue_depth.set_function(fn)

    def freeze_queue_depth(self) -> None:
        """Replace the live callback with 0 at engine stop (other engines'
        children are untouched)."""
        self.queue_depth.set(0.0)


_TTFT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                 1.0, 2.5, 5.0, 10.0)

# inter-token latency sits an order of magnitude below TTFT (one decode
# step vs queue+prefill), so the buckets start at the dispatch floor
_ITL_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5)


class GenerationMetrics:
    """Decode/continuous-batching families (``dl4j_decode_*``) — the one
    owner of their names/labels, same contract as ``ServingMetrics``.
    Per-instance gauges are labeled ``engine=`` with a process-unique id
    so a second generation engine neither clobbers nor zeroes the
    first's."""

    def __init__(self, registry=None, engine_id: str = None):
        reg = registry if registry is not None else get_registry()
        self.registry = reg
        self.engine_id = (engine_id if engine_id is not None
                          else f"g{next(_ENGINE_IDS)}")
        self.requests = reg.counter(
            "dl4j_decode_requests_total",
            "Generation requests by terminal outcome (length/stop = "
            "completed; cancelled/deadline/shutdown/error = not)",
            labels=("status",))
        self.tokens = reg.counter(
            "dl4j_decode_tokens_total",
            "Tokens generated and delivered to request streams",
            labels=("model",))
        self.steps = reg.counter(
            "dl4j_decode_steps_total",
            "Decode steps harvested (one per running-batch iteration)")
        self.decode_dispatch = reg.counter(
            "dl4j_decode_dispatch_total",
            "Decode-step dispatches by how far ahead of the host the loop "
            "ran: ahead = dispatched from ids on the device while the "
            "previous step's were not yet harvested (the steady state), "
            "sync = nothing was in flight (the first step after an idle "
            "loop or after an error)", labels=("mode",))
        self.discarded_rows = reg.counter(
            "dl4j_decode_discarded_rows_total",
            "Rows of a decode step whose id was dropped at its harvest: "
            "the request had ended at the harvest before (stop = its stop "
            "token, cancelled, deadline) while the step was in flight, so "
            "it ran one step more than it was served",
            labels=("reason",))
        self.prefix_pages = reg.counter(
            "dl4j_decode_prefix_pages_total",
            "Prompt pages at admission by outcome: shared counts pages an "
            "identical prefix let the request reference instead of "
            "prefilling fresh — BOTH in-flight sharing (another running "
            "request owns the page) and persistent prefix-cache hits "
            "(the radix tree kept it alive past its last request) land "
            "here; dl4j_prefix_cache_* tells the two apart",
            labels=("outcome",))
        # persistent radix-tree prefix cache (generation/prefix_cache.py)
        self.prefix_cache_hits = reg.counter(
            "dl4j_prefix_cache_hits",
            "Admissions whose prompt matched >= 1 cached radix-tree page "
            "(prefill priced at the suffix instead of the whole prompt)")
        self.prefix_cache_misses = reg.counter(
            "dl4j_prefix_cache_misses",
            "Admissions that matched nothing in the radix tree")
        self.prefix_cache_offloads = reg.counter(
            "dl4j_prefix_cache_offload_total",
            "Cold cached pages spilled device -> host tier (page slice "
            "copied out, device page freed, prefix still cached)")
        self.prefix_cache_restores = reg.counter(
            "dl4j_prefix_cache_restore_total",
            "Host-tier pages restored into fresh device pages on a hit")
        self.prefix_cache_evictions = reg.counter(
            "dl4j_prefix_cache_evictions_total",
            "Radix-tree nodes dropped outright, by reason (capacity = "
            "device room with no host budget left, host_capacity = host "
            "tier over budget, swap = weights changed, pool_reset = "
            "pools reseeded, abort = admission's prefill failed)",
            labels=("reason",))
        self.ttft = reg.histogram(
            "dl4j_decode_ttft_seconds",
            "Time to first token: submit -> first sampled token delivered "
            "(queue wait + prefill)", buckets=_TTFT_BUCKETS)
        self.inter_token = reg.histogram(
            "dl4j_decode_inter_token_seconds",
            "Inter-token latency: gap between consecutive delivered "
            "tokens of one request (the streaming-smoothness half of the "
            "decode SLO; TTFT is the other)", buckets=_ITL_BUCKETS)
        self.shed = reg.counter(
            "dl4j_decode_shed_total",
            "Generation requests shed by admission control, by reason",
            labels=("reason",))
        self.evictions = reg.counter(
            "dl4j_decode_evicted_total",
            "Requests removed from the RUNNING batch mid-flight (pages "
            "freed before completion), by reason",
            labels=("reason",))
        self.swaps = reg.counter(
            "dl4j_decode_model_swaps_total",
            "Completed generation-model hot-swaps", labels=("model",))
        self.param_casts = reg.counter(
            "dl4j_decode_param_casts_total",
            "Serving snapshots cast: a version's parameters copied into "
            "the compute dtype for its generation programs (one per "
            "loaded version, one more each time its net's parameter "
            "tree is found changed at a dispatch)", labels=("model",))
        self.layer_path_steps = reg.counter(
            "dl4j_layer_path_steps_total",
            "Dispatched decode steps (stage=decode) and prefills "
            "(stage=prefill), once for each distinct (kind, path) the "
            "program takes: kind is the layer's (Layer.kind), path what "
            "its Layer.serving_path names for the program's shapes, the "
            "rule its traced branch follows (attention: heads / rows / "
            "lax / gather for paged self-attention, paged / gathered / "
            "expanded for latent attention; experts: streamed / sorted / "
            "ragged; recurrent: step / scan / stepwise for state-space "
            "layers, delta_kernel / delta_step / delta_chunk / "
            "delta_stepwise for delta-rule layers); kind=head is the "
            "sampling epilogue's path from the rows' policy: greedy / "
            "draw / filter", labels=("stage", "kind", "path"))
        self.moe_tokens = reg.counter(
            "dl4j_moe_tokens_total",
            "Real tokens routed by the served net's expert layers, summed "
            "over those layers (bucket padding and idle slots excluded); "
            "counted on the device, harvested with the sampled ids")
        self.moe_held_assignments = reg.counter(
            "dl4j_moe_held_assignments_total",
            "Token-to-expert assignments that fell on an expert this "
            "engine's net holds, by the expert's index among the held "
            "ones, summed over expert layers; over "
            "dl4j_moe_tokens_total: top_k * held / n_experts when routing "
            "is uniform", labels=("expert",))
        self.state_slot_resets = reg.counter(
            "dl4j_state_slot_resets_total",
            "Admissions that began a state slot's recurrent state anew (a "
            "prefill from position 0 of a net with state slots: the row "
            "starts from zero whatever its last tenant left)",
            labels=("engine",)).labels(engine=self.engine_id)
        self.state_slots_in_use = reg.gauge(
            "dl4j_state_slots_in_use",
            "State slots held by running requests (a net with recurrent "
            "layers: one row of state a slot, held for the request's life)",
            labels=("engine",)).labels(engine=self.engine_id)
        self.mhc_row_sum_error = reg.gauge(
            "dl4j_mhc_row_sum_error",
            "Largest distance from 1 of a row sum or a column sum of H_res "
            "(the Sinkhorn-normalised mixing matrix of a hyper-connection "
            "block) over the real rows and the blocks of the last harvested "
            "decode step or prefill; the loop's last division is over rows, "
            "so the columns carry what it left undone: ~1e-3 after 20 "
            "iterations, tenths after one", labels=("engine",)
        ).labels(engine=self.engine_id)
        # per-instance children
        self.active_slots = reg.gauge(
            "dl4j_decode_active_slots",
            "Requests currently holding a decode slot",
            labels=("engine",)).labels(engine=self.engine_id)
        self.page_util = reg.gauge(
            "dl4j_decode_page_utilization",
            "Allocated fraction of the paged KV pool (trash page "
            "excluded)", labels=("engine",)).labels(engine=self.engine_id)
        self._kv_pages = {
            "in_use": reg.gauge(
                "dl4j_kv_pages_in_use",
                "Pages of the paged KV pools held by requests (or by the "
                "prefix cache), by layer kind: global = layers that keep "
                "every position, window = sliding-window layers, whose "
                "requests hold a ring that does not grow with the context",
                labels=("engine", "kind")),
            "total": reg.gauge(
                "dl4j_kv_pages_total",
                "Usable pages of the paged KV pools (trash page excluded), "
                "by layer kind", labels=("engine", "kind"))}
        self._kv_pages_children = {}
        self.prefix_cache_resident = reg.gauge(
            "dl4j_prefix_cache_resident_pages",
            "Device pages the prefix-cache radix tree currently keeps "
            "alive", labels=("engine",)).labels(engine=self.engine_id)
        self.prefix_cache_pinned = reg.gauge(
            "dl4j_prefix_cache_pinned_pages",
            "Cached pages protected by at least one session pin",
            labels=("engine",)).labels(engine=self.engine_id)
        self.prefix_cache_host_bytes = reg.gauge(
            "dl4j_prefix_cache_host_tier_bytes",
            "Bytes of offloaded KV page payloads held in the host-RAM "
            "tier", labels=("engine",)).labels(engine=self.engine_id)
        self.batch_occupancy = reg.histogram(
            "dl4j_decode_batch_occupancy",
            "Active slots per dispatched decode step / total slots (1.0 = "
            "every lane did useful work)",
            buckets=_UTIL_BUCKETS)

    def set_kv_pages(self, kind: str, in_use: int, total: int) -> None:
        """``dl4j_kv_pages_in_use{kind}`` and ``dl4j_kv_pages_total{kind}``
        of this engine; a kind's children exist from its first set, so a
        net without window layers reports ``kind="global"`` alone."""
        children = self._kv_pages_children.get(kind)
        if children is None:
            children = self._kv_pages_children[kind] = tuple(
                self._kv_pages[k].labels(engine=self.engine_id, kind=kind)
                for k in ("in_use", "total"))
        children[0].set(in_use)
        children[1].set(total)
