"""Multi-host bootstrap: the reference's Network::Init for the TPU world.

The reference brings up a TCP/MPI mesh from `num_machines` +
`machine_list_file` (+ `local_listen_port`) at training start
(/root/reference/src/application/application.cpp:185-197,
src/network/linkers_socket.cpp:73-110: "ip port" lines, optional
`rank=<n>` override, rank otherwise assigned by list order).

On TPU the transport is XLA's ICI/DCN collectives; what remains of the
network layer is PROCESS bootstrap: every host calls
`jax.distributed.initialize(coordinator, num_processes, process_id)`, after
which `jax.devices()` is the GLOBAL device list and the mesh learners
(learner/fused.py make_mesh) shard over all hosts' chips with zero further
changes — psum/all_gather ride ICI within a slice and DCN across slices.

Launch recipe (2 hosts x 4 chips each):
    # mlist.txt on both hosts:
    #   10.0.0.1 12400
    #   10.0.0.2 12400
    host0$ python -m lightgbm_tpu config=train.conf num_machines=2 \
               machine_list_file=mlist.txt        # rank inferred: local ip
    host1$ python -m lightgbm_tpu config=train.conf num_machines=2 \
               machine_list_file=mlist.txt
    # rank can be forced per host: LIGHTGBM_TPU_MACHINE_RANK=1 or a
    # `rank=1` suffix on the machine line, like the reference's parser.
"""
from __future__ import annotations

import os
import socket
import warnings
from typing import List, Optional, Tuple

_initialized = False


def parse_machine_list(path: str) -> List[Tuple[str, int, Optional[int]]]:
    """`ip port [rank=<n>]` per line (linkers_socket.cpp:73-110)."""
    out: List[Tuple[str, int, Optional[int]]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            rank = None
            toks = []
            for t in line.replace(",", " ").split():
                if t.startswith("rank="):
                    rank = int(t[5:])
                else:
                    toks.append(t)
            if len(toks) < 2:
                raise ValueError(
                    f"machine_list line needs 'ip port': {line!r}")
            out.append((toks[0], int(toks[1]), rank))
    return out


def _local_addresses() -> List[str]:
    addrs = {"127.0.0.1", "localhost"}
    try:
        hostname = socket.gethostname()
        addrs.add(hostname)
        for info in socket.getaddrinfo(hostname, None):
            addrs.add(info[4][0])
    except OSError:
        pass
    return list(addrs)


def resolve_rank(machines: List[Tuple[str, int, Optional[int]]]) -> int:
    """This process's rank: env override, then explicit rank= entries,
    then local-address match (the reference matches the local ip against
    the list the same way, linkers_socket.cpp:84-103)."""
    env = os.environ.get("LIGHTGBM_TPU_MACHINE_RANK")
    if env is not None:
        return int(env)
    local = set(_local_addresses())
    matches = [(i, rank) for i, (ip, _port, rank) in enumerate(machines)
               if ip in local]
    if len(matches) > 1:
        # same host listed more than once (multi-process single host):
        # the address alone cannot disambiguate the processes
        raise ValueError(
            "machine_list has multiple local entries; set "
            "LIGHTGBM_TPU_MACHINE_RANK per process to disambiguate")
    if matches:
        i, rank = matches[0]
        return rank if rank is not None else i
    raise ValueError(
        "cannot determine this machine's rank: none of the machine_list "
        "addresses are local; set LIGHTGBM_TPU_MACHINE_RANK")


def init_distributed(num_machines: int, machine_list_file: str = "",
                     local_listen_port: int = 12400) -> bool:
    """Bring up the multi-process JAX runtime.  Returns True if a
    multi-host world was initialized (idempotent; False for single-host).

    Maps the reference config exactly: `num_machines` processes, the
    coordinator is the FIRST machine in the list (reference rank 0), and
    `local_listen_port` is the fallback port when no list file is given
    (single-host multi-process testing: coordinator on localhost)."""
    global _initialized
    if num_machines <= 1:
        return False
    if _initialized:
        return True
    import jax
    if machine_list_file:
        machines = parse_machine_list(machine_list_file)
        if len(machines) != num_machines:
            raise ValueError(
                f"machine_list_file has {len(machines)} entries, "
                f"num_machines={num_machines}")
        rank = resolve_rank(machines)
        # the coordinator is the machine whose EFFECTIVE rank is 0 —
        # rank= overrides can move rank 0 away from the first list line
        coord_machine = machines[0]
        for i, m in enumerate(machines):
            eff = m[2] if m[2] is not None else i
            if eff == 0:
                coord_machine = m
                break
        coord = f"{coord_machine[0]}:{coord_machine[1]}"
    else:
        rank_env = os.environ.get("LIGHTGBM_TPU_MACHINE_RANK")
        if rank_env is None:
            warnings.warn(
                "num_machines>1 without machine_list_file or "
                "LIGHTGBM_TPU_MACHINE_RANK: assuming single-host test "
                "mode, skipping jax.distributed")
            return False
        rank = int(rank_env)
        coord = f"127.0.0.1:{local_listen_port}"
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=num_machines,
                               process_id=rank)
    _initialized = True
    return True


def maybe_init_from_config(cfg) -> bool:
    """Application entry (application.cpp:185-197 Network::Init analog)."""
    return init_distributed(int(getattr(cfg, "num_machines", 0) or 0),
                            getattr(cfg, "machine_list_file", ""),
                            int(getattr(cfg, "local_listen_port", 12400)))


# ---------------------------------------------------------------------------
# Distributed ingestion: pre-partitioned rows + global bin mappers
# (reference dataset_loader.cpp:554-659 row assignment and :733-833
# distributed bin finding)
# ---------------------------------------------------------------------------

def local_row_slice(n: int) -> slice:
    """This process's contiguous row block of an n-row dataset —
    the TPU-era analog of the reference's pre-partition row assignment
    (contiguous blocks instead of mod-assignment so binned stores stay
    gather-free)."""
    import jax
    world = jax.process_count()
    rank = jax.process_index()
    per = (n + world - 1) // world
    return slice(min(rank * per, n), min((rank + 1) * per, n))


def allgather_f64(arr) -> "np.ndarray":
    """Process-allgather a float64 array BIT-EXACTLY.

    jax with x64 disabled silently rounds float64 collective payloads to
    float32 — enough to perturb bin boundaries and init scores in their
    last ulps, which breaks the multi-process == single-process model
    equality the data-parallel scheme promises.  uint32 words survive
    the collective unchanged.  Returns [world, *arr.shape] float64."""
    import numpy as np
    from jax.experimental import multihost_utils
    a = np.ascontiguousarray(np.asarray(arr, np.float64))
    words = a.view(np.uint32)
    out = np.asarray(multihost_utils.process_allgather(words))
    # process_allgather returns [W, *words.shape] on a multi-process
    # world but the bare words.shape when W == 1 — normalize so the
    # documented [world, *arr.shape] contract holds for every caller
    out = np.ascontiguousarray(out).reshape((-1,) + words.shape)
    return out.view(np.float64)


def resolve_bin_find(cfg, n_sample_global: int, world: int = 1) -> str:
    """Resolve the `bin_find` knob to the path distributed bin finding
    runs.  "allgather" is the validated exact path (every rank derives
    mappers from the identical allgathered global sample);  "sketch"
    merges per-host quantile summaries (sharded/sketch.py) so no host
    ever materializes the global sample.  "auto" stays exact while the
    combined sample fits the bin-construction budget — the
    pre-partition loader caps each rank at `budget // world + 1` rows,
    so the `+ world` slack keeps its combined sample INSIDE the exact
    path (default distributed binning stays the validated allgather;
    sketches engage only when a caller feeds samples genuinely beyond
    the budget, or explicitly via bin_find=sketch)."""
    mode = getattr(cfg, "bin_find", "auto")
    if mode == "auto":
        budget = int(cfg.bin_construct_sample_cnt) + max(int(world), 1)
        return "sketch" if n_sample_global > budget else "allgather"
    return mode


def _gathered_sizes(n_local: int) -> "np.ndarray":
    import numpy as np
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(
        np.array([n_local], np.int64))).reshape(-1)


def _allgather_rows(local_rows, smax: int, sizes) -> "np.ndarray":
    """Allgather variable-length row blocks (padded to `smax`, sliced
    back by the true sizes) into one concatenated array."""
    import numpy as np
    padded = np.zeros((smax, local_rows.shape[1]), np.float64)
    padded[: len(local_rows)] = local_rows
    gathered = allgather_f64(padded)                      # [W, smax, F]
    return np.concatenate([gathered[w, : int(sizes[w])]
                           for w in range(gathered.shape[0])])


def find_bin_mappers_sketch(local_sample, cfg, categorical=(),
                            return_sample=False):
    """Global BinMappers by MERGING per-host quantile sketches — the
    distributed bin finding of the reference's Network layer
    (dataset_loader.cpp:733-833) rebuilt on mergeable summaries
    (arXiv:1706.08359 §4, arXiv:1806.11248 §5): each host summarizes
    its local sample into O(F / eps) weighted entries, ONE small
    allgather exchanges the fixed-width summaries, and every rank
    derives identical mappers from the deterministic rank-order merge.
    No host ever materializes the global sample.

    return_sample=True returns a BOUNDED plan sample alongside (for the
    EFB bundle planner, which needs row-level co-occurrence): each rank
    contributes at most BUNDLE_PLAN_SAMPLE_CNT / world rows, so the
    gathered sample is O(50k) rows regardless of the dataset — never
    the global sample."""
    import jax
    import numpy as np
    from .sharded.sketch import SketchSet, sketch_columns

    world = jax.process_count()
    ss = sketch_columns(local_sample, cfg, categorical=categorical)
    if world > 1:
        packed = ss.pack()                     # [F+1, 2*cap+4]
        stack = allgather_f64(packed)          # [W, F+1, 2*cap+4]
        ss = SketchSet.merge_packed(stack, categorical=categorical)
    mappers = ss.mappers_from_config(cfg)
    if not return_sample:
        return mappers
    from .dataset import BUNDLE_PLAN_SAMPLE_CNT
    cap = max(BUNDLE_PLAN_SAMPLE_CNT // max(world, 1), 1)
    plan_local = np.ascontiguousarray(
        np.asarray(local_sample, np.float64)[:cap])
    if world > 1:
        sizes = _gathered_sizes(len(plan_local))
        plan_sample = _allgather_rows(plan_local, int(sizes.max()), sizes)
    else:
        plan_sample = plan_local
    return mappers, plan_sample


def find_bin_mappers_distributed(local_sample, cfg, categorical=(),
                                 return_sample=False):
    """Global BinMappers from per-process local samples.

    Two paths behind the `bin_find` knob (resolve_bin_find):

    - "allgather" (the validated exact path): the sample rows are
      allgathered (one collective on a [S, F] float array) and every
      process derives identical mappers from the identical global
      sample — no mapper serialization format needed, determinism by
      construction.  The reference instead shards FEATURES across
      machines and allgathers serialized mappers
      (dataset_loader.cpp:733-833).
    - "sketch": per-host mergeable quantile summaries exchanged in ONE
      O(F / eps) collective (find_bin_mappers_sketch) — the path that
      scales past the sample budget, because no host ever holds the
      global sample.

    return_sample=True also returns an identical-on-every-rank sample,
    so rank-consistent derived decisions (the EFB bundle plan) can be
    computed from it without a second collective — the full global
    sample on the allgather path, a bounded O(50k)-row plan sample on
    the sketch path."""
    import jax
    import numpy as np
    from .binning import find_bin_mappers

    world = jax.process_count()
    if world > 1:
        sizes = _gathered_sizes(len(local_sample))
        n_global = int(sizes.sum())
    else:
        sizes = np.array([len(local_sample)], np.int64)
        n_global = len(local_sample)
    if resolve_bin_find(cfg, n_global, world) == "sketch":
        return find_bin_mappers_sketch(local_sample, cfg,
                                       categorical=categorical,
                                       return_sample=return_sample)

    if world == 1:
        m = find_bin_mappers(
            local_sample, cfg.max_bin, cfg.min_data_in_bin,
            cfg.min_data_in_leaf, categorical=categorical,
            sample_cnt=len(local_sample), seed=cfg.data_random_seed,
            use_missing=cfg.use_missing)
        return (m, local_sample) if return_sample else m

    # pad local samples to one shape (process sample sizes can differ by
    # one chunk); true per-process sizes travel alongside so padding rows
    # are sliced away exactly (no sentinel values — data may contain any)
    flat = _allgather_rows(local_sample, int(sizes.max()), sizes)
    cap = int(cfg.bin_construct_sample_cnt)
    if len(flat) > cap:
        idx = np.random.RandomState(cfg.data_random_seed).choice(
            len(flat), cap, replace=False)
        flat = flat[np.sort(idx)]
    m = find_bin_mappers(
        flat, cfg.max_bin, cfg.min_data_in_bin, cfg.min_data_in_leaf,
        categorical=categorical, sample_cnt=len(flat),
        seed=cfg.data_random_seed, use_missing=cfg.use_missing)
    return (m, flat) if return_sample else m
