import os
# The dry-run compiles for 512 forced host devices and never touches an
# accelerator, so it pins the CPU platform (on a TPU host it would otherwise
# build its meshes from the chips). The flags are appended, not overwritten.
_USER_XLA_FLAGS = os.environ.get("XLA_FLAGS", "")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    _USER_XLA_FLAGS + " --xla_force_host_platform_device_count=512").strip()

# ^ MUST run before ANY other import (jax locks the platform and device count
# at first initialisation). Everything below is ordinary.

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds the arch bundle and the train/prefill/decode artifacts,
  3. ``jit(...).lower(ShapeDtypeStructs).compile()`` -- no allocation,
  4. records ``memory_analysis()`` (proves the cell fits the per-chip HBM),
     ``cost_analysis()`` (FLOPs/bytes for §Roofline), and the collective
     statistics parsed from the compiled HLO (§Roofline's third term),
  5. derives the three roofline terms against TPU v5e constants.

Also lowers the paper's own workload (``--arch mam-snn``): the distributed
SNN engine window at full MAM scale, under both the conventional and the
structure-aware schedule -- the collective-bytes/op-count delta between the
two IS the paper's claim, visible in compiled HLO.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both \
      --out dryrun_results.json
  python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k \
      --mesh single --hierarchical
"""

import argparse
import dataclasses
import json
import math
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.common import SHAPES, ShapeSpec
from repro.configs.registry import arch_skips, get_arch, list_archs
from repro.launch.hlo_stats import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.optim.hierarchical import HierarchicalConfig
from repro.train.steps import make_serve_artifacts, make_train_artifacts

# TPU v5e-class hardware constants (per chip).
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s per link

SNN_ARCH = "mam-snn"


def _cost_get(cost: dict, key: str) -> float:
    try:
        return float(cost.get(key, 0.0))
    except AttributeError:
        return 0.0


def roofline_terms(flops: float, hbm_bytes: float, wire_bytes: float,
                   n_devices: int, total: bool) -> dict:
    """Three roofline terms in seconds (per device).

    ``total=True`` when flops/bytes are whole-program totals (divide by
    chips); False when they are already per-device.
    """
    div = n_devices if total else 1
    return {
        "compute_s": flops / div / PEAK_FLOPS,
        "memory_s": hbm_bytes / div / HBM_BW,
        "collective_s": wire_bytes / ICI_BW,
    }


def _dominant(terms: dict) -> str:
    return max(("compute_s", "memory_s", "collective_s"),
               key=lambda k: terms[k])


def modelled_hbm_gib(row: dict) -> float:
    """Per-device footprint (GiB) from XLA's memory_analysis on the row."""
    mem = row.get("memory_analysis") or {}
    return (mem.get("argument_bytes", 0) + mem.get("temp_bytes", 0)
            + mem.get("output_bytes", 0)) / 2**30


def enforce_hbm_budget(row: dict, budget_gib: float | None) -> dict:
    """Fail-fast HBM gate: the modelled per-device footprint must fit.

    Flips an OK row to FAIL (which trips the dry run's nonzero exit) when
    XLA's own memory analysis says the compiled cell cannot live within
    ``budget_gib`` per device -- the bound is recorded on the row either way
    so the JSON stays auditable.
    """
    if not budget_gib or row.get("status") != "OK":
        return row
    got = modelled_hbm_gib(row)
    row["hbm_gib_modelled"] = round(got, 3)
    row["hbm_gib_budget"] = budget_gib
    if got > budget_gib:
        row["status"] = (f"FAIL(HBM: modelled {got:.2f} GiB/device exceeds "
                         f"the --hbm-gib {budget_gib:g} budget)")
    return row


def _analyze(lowered, compiled, n_devices: int) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    # Trip-count-aware accounting: XLA's own cost_analysis counts each while
    # body once, which under-counts scan-stacked layers by ~L x n_micro; the
    # hlo_stats parser multiplies per-computation costs by loop trip counts.
    stats = analyze_hlo(hlo, n_devices)
    mem_info = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
    }
    # The SPMD-partitioned module is per-device: stats are per-device.
    # Memory term uses the *fused* bound (elementwise chains VMEM-resident,
    # as on TPU); the naive every-op bound is kept alongside in the row.
    terms = roofline_terms(stats.flops, stats.hbm_bytes_fused,
                           stats.total_wire_bytes, n_devices, total=False)
    terms["memory_naive_s"] = stats.hbm_bytes / HBM_BW
    return {
        "flops_per_device": stats.flops,
        "hbm_bytes_per_device": stats.hbm_bytes_fused,
        "hbm_bytes_naive_per_device": stats.hbm_bytes,
        "xla_cost_flops_raw": _cost_get(cost, "flops"),
        "memory_analysis": mem_info,
        "collectives": stats.as_dict(),
        "roofline": terms,
        "dominant": _dominant(terms),
    }


def model_flops(bundle, shape: ShapeSpec) -> float:
    """6 * N_active * tokens (train) / 2 * N_active * tokens (inference)."""
    n_active = bundle.cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    factor = 6.0 if shape.kind == "train" else 2.0
    return factor * n_active * tokens


def dryrun_lm_cell(arch_id: str, shape_name: str, multi_pod: bool,
                   hierarchical: bool) -> dict:
    shape = SHAPES[shape_name]
    skip = arch_skips(arch_id).get(shape_name)
    row: dict[str, Any] = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": ("hierarchical" if hierarchical else "sync"),
    }
    if skip:
        row["status"] = f"SKIP({skip})"
        return row

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = mesh.size
    dp_axes = (("pod", "data") if multi_pod and not (hierarchical
               and shape.kind == "train") else ("data",))
    # Activation/logits sharding constraints need the DP axis names; the
    # batch=1 long-context cell cannot shard its batch at all.
    act_axes = None if shape.name == "long_500k" else dp_axes
    # Attention activation layout: head-parallel when KV heads divide the
    # 16-way TP axis, else context-parallel (see models/layers.py).
    probe = get_arch(arch_id)
    n_kv = getattr(probe.cfg, "n_kv", None)
    if n_kv is None and hasattr(probe.cfg, "backbone"):
        n_kv = probe.cfg.backbone.n_kv
    if n_kv is None and probe.family == "audio":
        n_kv = probe.cfg.n_heads
    attn_mode = None
    if n_kv is not None:
        attn_mode = "heads" if n_kv % 16 == 0 else "seq"
    bundle = get_arch(arch_id, act_batch_axes=act_axes, attn_sharding=attn_mode)
    # FSDP policy: parameters below ~1B replicate (per-microbatch ZeRO-3
    # gathers cost more than they save); larger models shard over 'data'.
    fsdp_axis = "data" if bundle.cfg.param_count() >= 1e9 else None
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            hier_cfg = None
            if hierarchical and multi_pod:
                hier_cfg = HierarchicalConfig(sync_every=10, compression="int8")
            # Microbatch so each accumulation slice is one sample per DP
            # shard (the memory-minimal production setting).
            n_dp = math.prod(mesh.shape[a] for a in dp_axes)
            per_replica = shape.global_batch // (
                n_dp * (mesh.shape["pod"] if hier_cfg is not None else 1))
            n_micro = max(1, per_replica)
            art = make_train_artifacts(
                bundle, mesh=mesh,
                batch_axes=dp_axes,
                fsdp_axis=fsdp_axis,
                hier_cfg=hier_cfg,
                n_micro=n_micro,
            )
            batch_sds = art.batch_sds(bundle, shape, mesh)
            lowered = art.step_fn.lower(art.params_sds, art.opt_sds, batch_sds)
            compiled = lowered.compile()
            row.update(_analyze(lowered, compiled, n_devices))
            if hier_cfg is not None and art.sync_fn is not None:
                lowered_s = art.sync_fn.lower(art.params_sds, art.sync_sds)
                compiled_s = lowered_s.compile()
                row["sync_step"] = _analyze(lowered_s, compiled_s, n_devices)
                row["sync_every"] = hier_cfg.sync_every
        elif shape.kind == "prefill":
            art = make_serve_artifacts(bundle, shape, mesh, fsdp_axis=fsdp_axis)
            batch = bundle.input_specs(shape)
            del batch["labels"]
            b_specs = {k: P(art.batch_axes, *([None] * (len(v.shape) - 1)))
                       for k, v in batch.items()}
            batch_sds = {
                k: jax.ShapeDtypeStruct(
                    v.shape, v.dtype, sharding=NamedSharding(mesh, b_specs[k]))
                for k, v in batch.items()
            }
            lowered = art.prefill_fn.lower(art.params_sds, batch_sds)
            compiled = lowered.compile()
            row.update(_analyze(lowered, compiled, n_devices))
        else:  # decode
            art = make_serve_artifacts(bundle, shape, mesh, fsdp_axis=fsdp_axis)
            tok_sds = jax.ShapeDtypeStruct(
                (shape.global_batch, 1), jnp.int32,
                sharding=NamedSharding(mesh, art.token_spec))
            extra = {}
            if bundle.family == "audio":
                pass  # enc_out already part of state_sds
            idx_sds = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = art.decode_fn.lower(
                art.params_sds, art.state_sds, tok_sds, idx_sds)
            compiled = lowered.compile()
            row.update(_analyze(lowered, compiled, n_devices))

    row["status"] = "OK"
    row["compile_s"] = round(time.time() - t0, 1)
    mf = model_flops(bundle, shape)
    row["model_flops_total"] = mf
    hlo_total = row["flops_per_device"] * n_devices
    row["useful_flops_ratio"] = mf / hlo_total if hlo_total else 0.0
    return row


def verify_inter_table_bounds(
    n_shards: int = 2, subgroup: int = 2, seed: int = 12
) -> dict:
    """Laptop-scale instantiated-shard check behind the production SDS rows.

    The production ``--snn`` cells price their inter tables from
    ``network_sds`` width *bounds* (nothing is allocated). This builds a
    small real network, cuts the same inbound slices
    (``shard_inter_tables(mode='group', subgroup=...)``), and asserts the
    SDS bound brackets the instantiated bytes: same leading shard/lane
    axes, bound width >= the data-dependent width, and the instantiated
    bytes within the bound's padding slack. A violated bound FAILs the dry
    run -- the production memory claims are only as good as these bounds.
    """
    from repro.core.areas import mam_benchmark_spec
    from repro.core.connectivity import (
        build_network, network_sds, shard_inter_tables, slice_intra_tables)

    spec = mam_benchmark_spec(n_areas=4, n_per_area=64, k_intra=8, k_inter=12)
    row: dict[str, Any] = {
        "arch": SNN_ARCH, "shape": "table_bounds",
        "mesh": f"{n_shards}x{subgroup}", "mode": "verify",
    }
    # outgoing="intra" skips the outgoing *inter* inversion: the inbound
    # slices are cut from the incoming tensors (shard_inter_tables) and the
    # intra check only needs tgt_intra, so the dense [A, n_pad, K_out_e]
    # tables would be built, held, and never read.
    net = build_network(spec, seed=seed, size_multiple=8, outgoing="intra")
    sds = network_sds(spec, size_multiple=8, outgoing=True,
                      inter_shards=n_shards, subgroup=subgroup)
    cut = shard_inter_tables(net, n_shards, mode="group", subgroup=subgroup)
    syn_b = net.bytes_per_synapse()
    got = cut.tgt_inter_in
    bound = sds.tgt_inter_in
    if bound.shape[:2] != got.shape[:2]:
        raise AssertionError(
            f"SDS shard/lane axes {bound.shape[:2]} != instantiated "
            f"{got.shape[:2]}")
    if bound.shape[-1] < got.shape[-1]:
        raise AssertionError(
            f"SDS width bound {bound.shape[-1]} < instantiated "
            f"{got.shape[-1]}: the production rows under-price the tables")
    if cut.dout_inter_in.dtype != sds.dout_inter_in.dtype:
        raise AssertionError(
            f"SDS delay dtype {sds.dout_inter_in.dtype} != instantiated "
            f"{cut.dout_inter_in.dtype}")
    # Same bracket for the subgroup-sliced outgoing intra tables (the
    # other table the production rows price via a width bound).
    cut_i = slice_intra_tables(net, subgroup)
    if sds.tgt_intra.shape[:2] != cut_i.tgt_intra.shape[:2]:
        raise AssertionError(
            f"SDS intra lane axis {sds.tgt_intra.shape[:2]} != "
            f"instantiated {cut_i.tgt_intra.shape[:2]}")
    if sds.tgt_intra.shape[-1] < cut_i.tgt_intra.shape[-1]:
        raise AssertionError(
            f"SDS intra width bound {sds.tgt_intra.shape[-1]} < "
            f"instantiated {cut_i.tgt_intra.shape[-1]}: the production "
            f"rows under-price the intra tables")
    if cut_i.dout_intra.dtype != sds.dout_intra.dtype:
        raise AssertionError(
            f"SDS intra delay dtype {sds.dout_intra.dtype} != "
            f"instantiated {cut_i.dout_intra.dtype}")
    # Bytes of ONE device's slice, modelled vs instantiated.
    per_dev_model = int(np.prod(bound.shape[2:])) * syn_b
    per_dev_real = int(np.prod(got.shape[2:])) * syn_b
    row["bytes_per_device_modelled"] = per_dev_model
    row["bytes_per_device_instantiated"] = per_dev_real
    row["bound_slack"] = round(per_dev_model / max(per_dev_real, 1), 3)
    row["intra_bound_slack"] = round(
        sds.tgt_intra.shape[-1] / max(cut_i.tgt_intra.shape[-1], 1), 3)
    row["status"] = "OK"
    return row


def construction_cost_row(
    scale: float = 1.0, min_reduction: float = 4.0
) -> dict:
    """Modelled host peak RSS of constructing the production network.

    Prices the host-build path (``build_network(outgoing=True)`` + the two
    shard cuts: every global tensor plus all S x subgroup inbound slices
    resident in one process) against the sharded build (plan pass + one
    shard's draws, temporaries and output slice). Pure byte arithmetic from
    the same deterministic width bounds as the SDS rows -- nothing is
    allocated. At ``scale=1`` the reduction must clear ``min_reduction``
    (the PR's acceptance bar) or the row FAILs the dry run.
    """
    from repro.core.areas import mam_spec
    from repro.core.connectivity import construction_cost_model

    row: dict[str, Any] = {
        "arch": SNN_ARCH, "shape": f"mam_x{scale:g}_build",
        "mesh": "16x16", "mode": "construction",
    }
    spec = mam_spec(scale=scale)
    # Production structure-aware cut: 16 area groups x 16-lane subgroups.
    cm = construction_cost_model(spec, n_shards=16, subgroup=16,
                                 size_multiple=16)
    row["build_bytes_host_modelled"] = cm["build_bytes_host_modelled"]
    row["build_bytes_shard_modelled"] = cm["build_bytes_shard_modelled"]
    row["build_gib_host_modelled"] = round(
        cm["build_bytes_host_modelled"] / 2**30, 2)
    row["build_gib_shard_modelled"] = round(
        cm["build_bytes_shard_modelled"] / 2**30, 2)
    row["build_reduction"] = round(cm["reduction"], 1)
    if cm["reduction"] < min_reduction:
        row["status"] = (
            f"FAIL(construction: modelled host-RSS reduction "
            f"{cm['reduction']:.1f}x below the {min_reduction:g}x bar)")
    else:
        row["status"] = "OK"
    return row


def measure_build_rss(
    n_areas: int = 8, n_per_area: int = 4096,
    k_intra: int = 256, k_inter: int = 256,
    n_shards: int = 4, subgroup: int = 2, seed: int = 12,
) -> dict:
    """Measured host peak RSS: host build vs sharded build, real processes.

    Forks two fresh interpreters (so each path's ``ru_maxrss`` is its own,
    not inherited from this process's jax arena) over a mid-size network
    chosen large enough that table bytes dominate the ~quarter-GiB import
    baseline. Child A runs the host path -- global build + both shard cuts;
    child B runs the sharded path -- plan pass, then every (shard, lane)'s
    tables built one at a time (the per-process peak a real shard pays).
    The sharded peak must come in under the host peak or the row FAILs.
    """
    import subprocess
    import sys

    row: dict[str, Any] = {
        "arch": SNN_ARCH, "shape": "build_rss",
        "mesh": f"{n_shards}x{subgroup}", "mode": "construction",
    }
    common = (
        "import resource, sys\n"
        "from repro.core.areas import mam_benchmark_spec\n"
        "spec = mam_benchmark_spec(n_areas=%d, n_per_area=%d, k_intra=%d, "
        "k_inter=%d)\n" % (n_areas, n_per_area, k_intra, k_inter)
    )
    host_src = common + (
        "from repro.core.connectivity import (\n"
        "    build_network, shard_inter_tables, slice_intra_tables)\n"
        "net = build_network(spec, seed=%d, outgoing='intra')\n"
        "cut = shard_inter_tables(net, %d, mode='group', subgroup=%d)\n"
        "cut = slice_intra_tables(cut, %d)\n"
        "print(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)\n"
        % (seed, n_shards, subgroup, subgroup)
    )
    a_loc = n_areas // n_shards
    shard_src = common + (
        "from repro.core.connectivity import (\n"
        "    sharded_build_plan, build_shard_tables, build_lane_intra_tables)\n"
        "plan = sharded_build_plan(spec, %d, %d, mode='group', subgroup=%d)\n"
        "for s in range(%d):\n"
        "    areas = list(range(s * %d, (s + 1) * %d))\n"
        "    for lane in range(%d):\n"
        "        t = build_shard_tables(spec, %d, s, plan=plan, lane=lane)\n"
        "        del t\n"
        "        ti = build_lane_intra_tables(spec, %d, areas, lane, "
        "plan=plan)\n"
        "        del ti\n"
        "print(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)\n"
        % (seed, n_shards, subgroup, n_shards, a_loc, a_loc, subgroup,
           seed, seed)
    )

    def _peak_kib(src: str) -> int:
        # Children inherit JAX_PLATFORMS=cpu (the builds call jnp, and a
        # child must never reach for a chip this process could hold) but
        # not the forced 512-device flag.
        env = dict(os.environ, XLA_FLAGS=_USER_XLA_FLAGS)
        out = subprocess.run(
            [sys.executable, "-c", src], capture_output=True, text=True,
            env=env, check=True)
        return int(out.stdout.strip().splitlines()[-1])

    t0 = time.time()
    host_kib = _peak_kib(host_src)
    t1 = time.time()
    shard_kib = _peak_kib(shard_src)
    t2 = time.time()
    row["host_peak_rss_mib"] = round(host_kib / 1024, 1)
    row["sharded_peak_rss_mib"] = round(shard_kib / 1024, 1)
    row["rss_reduction"] = round(host_kib / max(shard_kib, 1), 2)
    row["host_build_s"] = round(t1 - t0, 1)
    row["sharded_build_s"] = round(t2 - t1, 1)
    if shard_kib >= host_kib:
        row["status"] = (
            f"FAIL(build RSS: sharded peak {shard_kib} KiB >= host peak "
            f"{host_kib} KiB -- the host-free build saved nothing)")
    else:
        row["status"] = "OK"
    return row


def dryrun_snn_cell(
    schedule: str,
    multi_pod: bool,
    scale: float = 1.0,
    backend: str = "",
    exchange: str = "",
    shard_tables: bool = True,
    subgroup_tables: bool = True,
    adaptive: bool = False,
) -> dict:
    """Lower the distributed SNN engine window at production MAM scale.

    ``backend`` selects the delivery backend (``event`` lowers the sparse
    id-packet paths -- the outgoing tables come from
    ``network_sds(outgoing=True)``, closing the dry-run gap); ``exchange``
    selects the global pathway (``routed`` lowers the ppermute rounds; with
    no spec-level adjacency the MAM graph is all-to-all, so routing skips
    nothing but the per-edge packets still lower). ``shard_tables``
    (default) lowers the sharded inbound inter receive tables
    (``network_sds(inter_shards=...)`` -- per-device table bytes divided by
    ~the shard count); False keeps the replicated-table baseline the
    sharded layout is measured against. The per-device table bytes and
    receive-side work land in ``row["inter_tables"]``. ``adaptive`` lowers
    the two-phase bucket-ladder exchange (count collective + lax.switch
    over pre-compiled payload sizes); ``row["wire_bytes_window"]`` then
    carries both the static worst case and the adaptive byte model, so the
    dry-run rows stay honest about what an adaptive run would actually
    ship.
    """
    from repro.core.areas import mam_spec
    from repro.core.connectivity import area_adjacency, network_sds
    from repro.core.dist_engine import network_pspecs, state_pspecs
    from repro.core.factory import make_simulation
    from repro.core.engine import EngineConfig
    from repro.core import delivery as delivery_lib
    from repro.core import exchange as exchange_lib
    from repro.core import neuron as neuron_lib

    label = "_".join(x for x in (schedule, backend, exchange) if x)
    if not shard_tables:
        label += "_reptables"
    elif not subgroup_tables:
        label += "_nosub"
    if adaptive:
        label += "_adaptive"
    row: dict[str, Any] = {
        "arch": SNN_ARCH, "shape": f"mam_x{scale:g}_{label}",
        "mesh": "2x16x16" if multi_pod else "16x16", "mode": schedule,
    }
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = mesh.size
    spec = mam_spec(scale=scale)
    # pad so both the 16-way subgroup and (for conventional) all 512 divide
    mult = 512 if schedule == "conventional" else 16
    needs_outgoing = backend == "event" or exchange == "routed"
    gsz = mesh.shape["model"]
    n_groups = n_devices // gsz
    n_shards = n_groups if schedule == "structure_aware" else n_devices
    shard_mode = "group" if schedule == "structure_aware" else "window"
    # The subgroup (window-within-group) slice only exists under the
    # structure-aware group cut; the conventional "window" cut is already
    # per-device.
    sub = (gsz if shard_tables and subgroup_tables
           and schedule == "structure_aware" else 1)
    net_sds = network_sds(
        spec, size_multiple=mult, outgoing=needs_outgoing,
        inter_shards=(n_shards if needs_outgoing and shard_tables else 0),
        inter_shard_mode=shard_mode, subgroup=sub)
    cfg = EngineConfig(neuron_model="lif", schedule=schedule,
                       delivery_backend=backend, exchange=exchange,
                       shard_inter_tables=shard_tables,
                       subgroup_inter_tables=subgroup_tables,
                       adaptive_exchange=adaptive)
    eng = make_simulation(spec, cfg, net=net_sds, mesh=mesh)
    if needs_outgoing and spec.k_inter > 0:
        # Static per-device receive-table accounting, replicated vs sharded
        # (the tentpole's memory claim, independent of XLA's analysis).
        routing = None
        if exchange == "routed":
            routing = exchange_lib.build_routing(
                area_adjacency(net_sds, spec), n_groups,
                exp_area_spikes=delivery_lib.expected_area_spikes(net_sds),
                headroom=cfg.s_max_headroom, floor=cfg.s_max_floor)
        row["inter_tables"] = exchange_lib.priced_inter_table_report(
            net_sds, n_groups=n_groups, gsz=gsz, schedule=schedule,
            headroom=cfg.s_max_headroom, floor=cfg.s_max_floor,
            routing=routing, subgroup=sub)
    if needs_outgoing and net_sds.tgt_inter_in is not None:
        # Mirror the engine's event-path drop of the dense incoming inter
        # tensors (never read once the inbound slices are cut) in the
        # lowering arguments, so memory_analysis().argument_bytes prices
        # what a production run actually holds -- not both layouts at once.
        k_e = net_sds.k_inter
        net_sds = dataclasses.replace(
            net_sds,
            src_inter=jax.ShapeDtypeStruct(
                (0, 0, k_e), net_sds.src_inter.dtype),
            w_inter=jax.ShapeDtypeStruct(
                (0, 0, k_e), net_sds.w_inter.dtype),
            delay_inter=jax.ShapeDtypeStruct(
                (0, 0, k_e), net_sds.delay_inter.dtype),
        )
    A, n_pad = net_sds.alive.shape
    R = net_sds.ring_len

    st_specs = state_pspecs(mesh, schedule, "lif")
    nt_specs = network_pspecs(mesh, schedule, like=net_sds)
    sds = jax.ShapeDtypeStruct

    def shard(x, spec_):
        return sds(x.shape, x.dtype, sharding=NamedSharding(mesh, spec_))

    state_sds = jax.tree.map(
        lambda leaf, spec_: shard(leaf, spec_),
        {
            "neuron": neuron_lib.LIFState(
                v=sds((A, n_pad), jnp.float32),
                i_syn=sds((A, n_pad), jnp.float32),
                refrac=sds((A, n_pad), jnp.int32),
            ),
            "ring": sds((A, n_pad, R), jnp.float32),
            "t": sds((), jnp.int32),
            "spike_count": sds((A, n_pad), jnp.int32),
            "overflow": sds((), jnp.int32),
            "shipped_bytes": sds((), jnp.float32),
        },
        {
            "neuron": st_specs.neuron, "ring": st_specs.ring,
            "t": st_specs.t, "spike_count": st_specs.spike_count,
            "overflow": st_specs.overflow,
            "shipped_bytes": st_specs.shipped_bytes,
        },
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)),
    )
    from repro.core.engine import SimState
    state_sds = SimState(**state_sds)
    net_in = jax.tree.map(
        lambda leaf, spec_: shard(leaf, spec_), net_sds, nt_specs,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct, P)),
    )
    gid_spec = (st_specs.spike_count)  # same layout as per-neuron arrays
    gids_sds = shard(sds((A, n_pad), jnp.int32), gid_spec)

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(eng.window_raw).lower(state_sds, net_in, gids_sds)
        compiled = lowered.compile()
    row.update(_analyze(lowered, compiled, n_devices))
    row["status"] = "OK"
    row["compile_s"] = round(time.time() - t0, 1)
    row["n_neurons"] = spec.n_total
    row["n_synapses_per_neuron"] = spec.k_total
    row["delay_ratio_D"] = spec.delay_ratio
    row["wire_bytes_window"] = eng.wire_bytes
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id | 'all' | 'mam-snn' (comma separated ok)")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--hierarchical", action="store_true",
                    help="use the paper-technique trainer (multi-pod only)")
    ap.add_argument("--snn-schedule", default="structure_aware")
    ap.add_argument("--snn-scale", type=float, default=1.0)
    ap.add_argument("--snn-backend", default="",
                    help="delivery backend for the SNN cells "
                         "('' = config default, 'event' lowers the sparse "
                         "id-packet paths via outgoing-table SDS)")
    ap.add_argument("--snn-exchange", default="",
                    help="global pathway for the SNN cells "
                         "('' = dense, 'routed' lowers the ppermute rounds)")
    ap.add_argument("--snn-replicated-tables", action="store_true",
                    help="lower the legacy replicated inter receive tables "
                         "instead of the sharded inbound slices (the "
                         "before/after baseline of the sharded-table PR)")
    ap.add_argument("--snn-no-subgroup-tables", action="store_true",
                    help="keep the PR 4 per-group inbound slices instead of "
                         "the subgroup-sliced [S, gsz, rows, K_in] layout "
                         "(the before/after baseline of the memory-diet PR)")
    ap.add_argument("--snn-adaptive", action="store_true",
                    help="lower the adaptive two-phase exchange (phase-1 "
                         "count collective + bucket-ladder payloads via "
                         "lax.switch) instead of static s_max packets")
    ap.add_argument("--hbm-gib", type=float, default=16.0,
                    help="per-device HBM budget (GiB) enforced on the SNN "
                         "rows: a cell whose modelled footprint (argument + "
                         "temp + output bytes from XLA's memory_analysis) "
                         "exceeds this FAILs the dry run instead of just "
                         "printing the number (default 16, the v5e chip; "
                         "0 disables the gate)")
    ap.add_argument("--build-rss", action="store_true",
                    help="also *measure* construction host peak RSS: fork "
                         "one fresh interpreter per build path (host build "
                         "+ shard cuts vs plan + per-shard builders) over a "
                         "mid-size network and FAIL unless the sharded "
                         "build's ru_maxrss comes in under the host "
                         "build's")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    rows = []
    if SNN_ARCH in archs:
        # Fail fast if the SDS width bounds the production rows are priced
        # from do not bracket an instantiated laptop-scale shard.
        try:
            rows.append(verify_inter_table_bounds())
        except Exception as e:
            rows.append({
                "arch": SNN_ARCH, "shape": "table_bounds",
                "mesh": "2x2", "status": f"FAIL({type(e).__name__}: {e})",
            })
            traceback.print_exc()
        _print_row(rows[-1])
        # Construction rows: what building the production network costs the
        # host, before any window runs -- the host-free build's claim.
        try:
            rows.append(construction_cost_row(args.snn_scale))
        except Exception as e:
            rows.append({
                "arch": SNN_ARCH, "shape": "build",
                "mesh": "16x16", "status": f"FAIL({type(e).__name__}: {e})",
            })
            traceback.print_exc()
        _print_row(rows[-1])
        if args.build_rss:
            try:
                rows.append(measure_build_rss())
            except Exception as e:
                rows.append({
                    "arch": SNN_ARCH, "shape": "build_rss",
                    "mesh": "4x2",
                    "status": f"FAIL({type(e).__name__}: {e})",
                })
                traceback.print_exc()
            _print_row(rows[-1])
    for multi_pod in meshes:
        for arch in archs:
            if arch == SNN_ARCH:
                # --snn-schedule "" runs only the verify/construction rows
                # (no production lowering) -- the CI construction gate.
                for sched in filter(None, args.snn_schedule.split(",")):
                    try:
                        rows.append(enforce_hbm_budget(dryrun_snn_cell(
                            sched, multi_pod, args.snn_scale,
                            backend=args.snn_backend,
                            # routed applies to the structure-aware lumped
                            # pathway only; conventional stays dense.
                            exchange=(args.snn_exchange
                                      if sched == "structure_aware" else ""),
                            shard_tables=not args.snn_replicated_tables,
                            subgroup_tables=not args.snn_no_subgroup_tables,
                            adaptive=args.snn_adaptive), args.hbm_gib))
                    except Exception as e:
                        rows.append({
                            "arch": arch, "shape": sched,
                            "mesh": "2x16x16" if multi_pod else "16x16",
                            "status": f"FAIL({type(e).__name__}: {e})",
                        })
                        traceback.print_exc()
                    _print_row(rows[-1])
                continue
            for shape in shapes:
                try:
                    rows.append(dryrun_lm_cell(arch, shape, multi_pod,
                                               args.hierarchical))
                except Exception as e:
                    rows.append({
                        "arch": arch, "shape": shape,
                        "mesh": "2x16x16" if multi_pod else "16x16",
                        "status": f"FAIL({type(e).__name__}: {e})",
                    })
                    traceback.print_exc()
                _print_row(rows[-1])

    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"\nwrote {len(rows)} rows to {args.out}")

    n_ok = sum(r["status"] == "OK" for r in rows)
    n_skip = sum(r["status"].startswith("SKIP") for r in rows)
    n_fail = len(rows) - n_ok - n_skip
    print(f"\n=== dry-run summary: {n_ok} OK, {n_skip} SKIP, {n_fail} FAIL ===")
    if n_fail:
        raise SystemExit(1)


def _print_row(row: dict) -> None:
    status = row.get("status", "?")
    base = f"[{row['mesh']}] {row['arch']:28s} {row['shape']:12s} "
    if status != "OK":
        print(base + status)
        return
    if "build_reduction" in row:  # modelled construction row
        print(base + f"OK build host={row['build_gib_host_modelled']}GiB "
              f"sharded={row['build_gib_shard_modelled']}GiB "
              f"({row['build_reduction']}x)")
        return
    if "rss_reduction" in row:  # measured construction row
        print(base + f"OK build-rss host={row['host_peak_rss_mib']}MiB/"
              f"{row['host_build_s']}s "
              f"sharded={row['sharded_peak_rss_mib']}MiB/"
              f"{row['sharded_build_s']}s ({row['rss_reduction']}x)")
        return
    if "roofline" not in row:  # bounds-verify row: no lowering behind it
        print(base + f"OK modelled={row['bytes_per_device_modelled']}B "
              f"instantiated={row['bytes_per_device_instantiated']}B "
              f"slack={row['bound_slack']}x")
        return
    r = row["roofline"]
    per_dev_gb = modelled_hbm_gib(row)
    tables = ""
    if "inter_tables" in row:
        tb = row["inter_tables"]["table_bytes"]
        tables = (f" inter-tables rep={tb['replicated'] / 2**30:.1f}GiB "
                  f"sharded={tb['sharded'] / 2**30:.1f}GiB "
                  f"({tb['reduction']:.1f}x)")
    print(base + f"OK compute={r['compute_s']*1e3:9.3f}ms "
          f"memory={r['memory_s']*1e3:9.3f}ms "
          f"collective={r['collective_s']*1e3:9.3f}ms "
          f"dom={row['dominant'][:-2]:10s} mem/dev={per_dev_gb:7.2f}GiB "
          f"compile={row.get('compile_s', 0):6.1f}s" + tables)


if __name__ == "__main__":
    main()
