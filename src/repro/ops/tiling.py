"""Shared tile-size selection + the per-(op, shape, dtype, platform) tuning
cache.

Every Pallas wrapper used to carry its own block chooser (``_choose_blocks``
in conv_window, ``_pick_rb`` in addtree, ``_pick`` in qmatmul). They are
folded here so one layer owns the heuristics, and a measured tuning cache
can override them uniformly:

    resolution order:  ExecPolicy.tiling overrides
                     > TuningCache entry for (op, shape-sig, dtype, platform)
                     > analytic heuristic

The cache is populated by *measurement*: ``repro.ops.autotune`` times a
candidate grid per (op, shape, dtype) and writes the winner
(``benchmarks/op_sweep.py`` and ``ExecutionPlan`` bind-time autotuning both
route through it). This is the software analogue of the FPGA design-space
exploration step in the accelerator surveys (DESIGN.md §7, §10).

Persistence is a versioned JSON file (``SCHEMA_VERSION``): load-on-start via
the ``REPRO_TUNING_CACHE`` env var or an explicit ``TUNING_CACHE.load(path)``
(``--tuning-cache`` on ``launch/serve.py`` / ``benchmarks/run.py``).
Corrupt or unknown-version files never poison a run — ``load`` warns and
returns 0, leaving the analytic heuristics in charge.
"""
from __future__ import annotations

import json
import math
import os
import pathlib
import warnings
from typing import Mapping

import numpy as np

from repro.core.window import band_input_rows

__all__ = ["legal_block", "window_vmem_bytes", "conv_layout",
           "lane_vmem_bytes", "PATCH_ROWS", "patch_channels",
           "patch_vmem_bytes",
           "choose_conv_blocks", "choose_lane_blocks", "choose_patch_blocks",
           "choose_fused_blocks", "choose_qmatmul_blocks",
           "legal_qmatmul_tiles",
           "choose_tree_rows", "TuningCache", "TUNING_CACHE", "tile_params",
           "conv_signature", "SCHEMA_VERSION"]

# VMEM one grid step may occupy, double-buffered blocks included. The
# Mosaic compiler's default scoped-VMEM limit on v5e is 16 MiB; half of it
# leaves room for in-kernel temporaries.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

# Mosaic's block rule: each of a block's last two dims is a multiple of
# the hardware tile (SUBLANE rows × LANE lanes for 32-bit data, 32 rows
# for int8) or the whole array dim.
SUBLANE = 8
LANE = 128
INT8_SUBLANE = 32

# output rows one contraction of the whole-window conv covers: two rows
# share one patch, so the stem's 64 channels fill the MXU's 128 rows
# (kernels/conv_window/patches.py); its row blocks are multiples of it
PATCH_ROWS = 2

# version of the persisted tuning-cache JSON schema (bumped when the key or
# row layout changes, or what a row's tiles mean; older/newer files fall
# back to heuristics on load). 2: a ``conv2d`` entry's ``mb`` is a lane
# block wherever the conv takes the channels-on-lanes layout.
SCHEMA_VERSION = 2


def _platform() -> str:
    import jax
    return jax.default_backend()


def legal_block(dim: int, cap: int, align: int) -> int:
    """Block size for a dim that sits on one of a block's last two axes:
    the whole dim when it fits ``cap``, else the largest divisor <= cap
    that is a multiple of ``align`` (the hardware tile), else the whole
    dim — the only sizes Mosaic's block rule accepts."""
    if dim <= cap:
        return dim
    for b in range(cap // align * align, 0, -align):
        if dim % b == 0:
            return b
    return dim


def _round_up(v: int, a: int) -> int:
    return -(-v // a) * a


def conv_signature(x_shape, w_shape, stride) -> tuple[int, ...]:
    """The tuning-cache shape signature shared by the ``conv2d`` and
    ``fused_conv_block`` wrappers and the autotuner:
    (B, N, H, W, M, Kh, Kw, sh, sw). Batch is part of the key — the
    batch-block candidate ``bb`` only makes sense per batch size."""
    bsz, n, h, w = x_shape
    m, _, kh, kw = w_shape
    return (bsz, n, h, w, m, kh, kw, *stride)


def window_vmem_bytes(n: int, w: int, kh: int, kw: int,
                      stride: tuple[int, int], mb: int, rows: int, bb: int,
                      itemsize: int, *, pooled: bool) -> int:
    """VMEM one grid step of a window kernel (kernels/conv_window,
    kernels/fused_cwp) occupies in their (8, 128)-tiled layouts: the
    halo'd slab (B, rows_in, P, N, W/P), the per-kernel-row weights (Kh,
    MB, Kw·N), the per-channel scale/bias columns and the output block, all
    double-buffered by the Pallas pipeline. ``rows`` counts the block's
    output rows — pooled rows when ``pooled``, each covering two conv rows
    and splitting the columns into even/odd phases."""
    sh, sw = stride
    groups = 2 if pooled else 1
    phases = groups * sw
    rows_in = band_input_rows(groups * rows, kh, sh)
    wo = (w - kw) // sw + 1
    slab = (bb * rows_in * phases * _round_up(n, SUBLANE)
            * _round_up(-(-w // phases), LANE))
    taps = kh * _round_up(mb, SUBLANE) * _round_up(kw * n, LANE)
    vecs = 2 * _round_up(mb, SUBLANE) * LANE
    out = bb * rows * _round_up(mb, SUBLANE) * _round_up(wo // groups, LANE)
    return 2 * (slab + taps + vecs + out) * itemsize


def _largest_rows(n, w, kh, kw, stride, mb, itemsize, full, *, pooled):
    """Largest row block in [1, full] whose grid step fits the budget
    (1 is the floor — a single-row block is always issued)."""
    best = 1
    for rows in range(1, full + 1):
        if window_vmem_bytes(n, w, kh, kw, stride, mb, rows, 1, itemsize,
                             pooled=pooled) > VMEM_BUDGET_BYTES:
            break
        best = rows
    return best


def conv_layout(n: int, wo: int, kh: int, kw: int) -> str:
    """The operand layout of the window conv kernel at this shape:

    - ``"lanes"`` (channels on the MXU's lanes) where the input channels
      fill more of a 128-lane pass than the output width does, and at
      least half of it;
    - ``"patches"`` (the whole Kh·Kw·N window contracted at once) where a
      kernel row of taps, Kw·N, fills under a quarter of the MXU's depth
      and the whole window fills at least one pass;
    - else ``"slab"`` (output width on the lanes, one Kw·N contraction
      per kernel row).

    Under 64 channels a tap of the lane kernel contracts less than half
    the MXU's depth, where the slab contracts a kernel row's Kw·N at
    once; and the slab is the contraction ``kernels/fused_cwp`` runs, so
    such a conv computes the same sums fused or not (the paper CNN's
    15-channel conv2, DESIGN.md §8). A window too small to fill one pass
    gains nothing from the patch, and keeps the slab too."""
    if min(n, LANE) > min(wo, LANE) and n >= LANE // 2:
        return "lanes"
    if kw * n < LANE // 4 and kh * kw * n >= LANE:
        return "patches"
    return "slab"


def lane_vmem_bytes(n: int, w: int, kh: int, kw: int,
                    stride: tuple[int, int], mb: int, rows: int, bb: int,
                    itemsize: int) -> int:
    """VMEM one grid step of the channels-on-lanes window conv occupies,
    in the same tiled reckoning as ``window_vmem_bytes``: Wo_pad on
    sublanes, N and MB on lanes. Double-buffered blocks: the halo'd slab
    (BB, rows_in, sh·sw, Wq, N), the tap weights (Kh·Kw, N, MB), the bias
    row and the output block (BB, RB, Wo_pad, MB); plus, once, the
    float32 temporaries of the contraction: one kernel row's operand
    (BB·RB·Wo_pad, Kw·N, or N per tap) and the accumulator. Wo_pad is Wo
    rounded up to the sublane tile; a 1x1 conv reads a subsampled input
    (kernels/conv_window), so its slab has one phase."""
    sub = SUBLANE * 4 // itemsize
    sh, sw = (1, 1) if kh == kw == 1 else stride
    wo_pad = _round_up((w - kw) // stride[1] + 1, sub)
    rows_in = rows + (kh - 1) // sh
    wq = wo_pad + (kw - 1) // sw
    k = kw * n if n % LANE == 0 else n
    slab = bb * rows_in * sh * sw * _round_up(wq, sub) * _round_up(n, LANE)
    taps = kh * kw * _round_up(n, sub) * _round_up(mb, LANE)
    vecs = sub * _round_up(mb, LANE)
    out = bb * rows * wo_pad * _round_up(mb, LANE)
    temps = bb * rows * wo_pad * (_round_up(k, LANE) + _round_up(mb, LANE))
    return 2 * (slab + taps + vecs + out) * itemsize + 4 * temps


def patch_channels(n: int, phases: int, itemsize: int) -> int:
    """Np, the input channels of the whole-window conv padded so that
    its P = stride_w column phases of Np channels fill whole sublane
    tiles (kernels/conv_window/patches.py)."""
    sub = SUBLANE * 4 // itemsize
    step = sub // math.gcd(phases, sub)
    return _round_up(n, step)


def patch_vmem_bytes(n: int, w: int, kh: int, kw: int,
                     stride: tuple[int, int], mb: int, rows: int, bb: int,
                     itemsize: int) -> int:
    """VMEM one grid step of the whole-window conv occupies, in the same
    tiled reckoning as ``window_vmem_bytes``, ``rows`` rounded up to a
    multiple of ``PATCH_ROWS`` (G). Double-buffered blocks: the halo'd
    slab (BB, rows_in, P·Np, Wo + ⌈Kw/P⌉ - 1), the stacked weights (G·MB,
    K) with K = ((G-1)·sh + Kh)·⌈Kw/P⌉·P·Np, the bias column and the
    output block (BB, RB, MB, Wo); plus, once, the float32 patch (K, Wo)
    and accumulator (G·MB, Wo)."""
    sub = SUBLANE * 4 // itemsize
    sh, sw = stride
    rows = _round_up(rows, PATCH_ROWS)
    wo = (w - kw) // sw + 1
    offs = -(-kw // sw)
    tile = sw * patch_channels(n, sw, itemsize)        # P·Np sublanes
    k = ((PATCH_ROWS - 1) * sh + kh) * offs * tile
    lanes = _round_up(wo + offs - 1, LANE)
    slab = bb * band_input_rows(rows, kh, sh) * tile * lanes
    wts = _round_up(PATCH_ROWS * mb, sub) * _round_up(k, LANE)
    vecs = _round_up(mb, sub) * LANE
    out = bb * rows * _round_up(mb, sub) * _round_up(wo, LANE)
    temps = (_round_up(k, sub) + _round_up(PATCH_ROWS * mb, sub)) \
        * _round_up(wo, LANE)
    return 2 * (slab + wts + vecs + out) * itemsize + 4 * temps


def _least_padding(full: int, cap: int) -> int:
    """The block in [1, cap] that pads ``full`` least (the larger on a
    tie): 8 under a cap of 5 gives 4, 56 under 20 gives 14."""
    return min(range(1, max(cap, 1) + 1),
               key=lambda v: (-(-full // v) * v, -v))


def choose_lane_blocks(b: int, n: int, h: int, w: int, m: int, kh: int,
                       kw: int, stride: tuple[int, int], itemsize: int
                       ) -> dict[str, int]:
    """Heuristic (rb, mb, bb) for the channels-on-lanes window conv.

    mb = a 256-lane block (128 where the weights of 256 lanes leave no
    room). Then the most output rows a step fits under
    ``VMEM_BUDGET_BYTES`` (``lane_vmem_bytes``), and once a step holds a
    whole image, the most images: rows x width x images form the
    contraction's streamed dimension, so the fuller the step, the fuller
    each MXU pass. Each is the block that pads its axis least.
    """
    ho = (h - kh) // stride[0] + 1

    def fits(mb, rows, bb):
        return lane_vmem_bytes(n, w, kh, kw, stride, mb, rows, bb,
                               itemsize) <= VMEM_BUDGET_BYTES

    mb = legal_block(m, 2 * LANE, LANE)
    if not fits(mb, 1, 1):
        mb = legal_block(m, LANE, LANE)
    rows = max([r for r in range(1, ho + 1) if fits(mb, r, 1)], default=1)
    rb = _least_padding(ho, rows)
    bb = 1
    if rb == ho:
        imgs = max([i for i in range(1, b + 1) if fits(mb, ho, i)],
                   default=1)
        bb = _least_padding(b, imgs)
    return {"rb": rb, "mb": mb, "bb": bb}


def choose_patch_blocks(b: int, n: int, h: int, w: int, m: int, kh: int,
                        kw: int, stride: tuple[int, int], itemsize: int
                        ) -> dict[str, int]:
    """Heuristic (rb, mb, bb) for the whole-window conv: mb = min(m, 128)
    rounded to a legal sublane block, then the most output rows a step
    fits under ``VMEM_BUDGET_BYTES`` (``patch_vmem_bytes``) and, once a
    step holds a whole image, the most images; each the block that pads
    its axis least, rows in multiples of ``PATCH_ROWS``."""
    ho = (h - kh) // stride[0] + 1
    mb = legal_block(m, LANE, SUBLANE)

    def fits(rows, bb):
        return patch_vmem_bytes(n, w, kh, kw, stride, mb, rows, bb,
                                itemsize) <= VMEM_BUDGET_BYTES

    groups = -(-ho // PATCH_ROWS)
    most = max([g for g in range(1, groups + 1)
                if fits(g * PATCH_ROWS, 1)], default=1)
    rb = PATCH_ROWS * _least_padding(groups, most)
    bb = 1
    if rb >= ho:
        imgs = max([i for i in range(1, b + 1) if fits(rb, i)], default=1)
        bb = _least_padding(b, imgs)
    return {"rb": rb, "mb": mb, "bb": bb}


def choose_conv_blocks(n: int, h: int, w: int, m: int, kh: int, kw: int,
                       stride: tuple[int, int], itemsize: int,
                       batch: int = 1) -> dict[str, int]:
    """Heuristic (rb, mb, bb) for the window-stationary conv kernel, in
    the layout ``conv_layout`` gives the shape: ``choose_lane_blocks``
    for channels on lanes, ``choose_patch_blocks`` for whole-window
    patches; for the slab, as below.

    mb = min(m, 128) (MXU lane width) rounded to a legal block, then the
    largest rb whose grid step fits ``VMEM_BUDGET_BYTES``
    (``window_vmem_bytes``). Rows sit on a leading block axis, so any rb
    is legal. ``bb`` (images per grid step) stays 1 here — batching the
    grid trades VMEM for weight reuse, a measured decision left to the
    autotuner (DESIGN.md §10).
    """
    ho = (h - kh) // stride[0] + 1
    layout = conv_layout(n, (w - kw) // stride[1] + 1, kh, kw)
    if layout == "lanes":
        return choose_lane_blocks(batch, n, h, w, m, kh, kw, stride,
                                  itemsize)
    if layout == "patches":
        return choose_patch_blocks(batch, n, h, w, m, kh, kw, stride,
                                   itemsize)
    mb = legal_block(m, 128, SUBLANE)
    rb = _largest_rows(n, w, kh, kw, stride, mb, itemsize, ho, pooled=False)
    return {"rb": rb, "mb": mb, "bb": 1}


def choose_fused_blocks(n: int, h: int, w: int, m: int, kh: int, kw: int,
                        stride: tuple[int, int], itemsize: int
                        ) -> dict[str, int]:
    """Heuristic (pb, mb, bb) for the fused conv+relu+pool kernel
    (kernels/fused_cwp). ``pb`` counts *pooled* rows: one block covers
    2·pb conv rows. ``bb`` defaults to 1 (see ``choose_conv_blocks``);
    the autotuner measures larger values."""
    po = max(((h - kh) // stride[0] + 1) // 2, 1)
    mb = legal_block(m, 128, SUBLANE)
    pb = _largest_rows(n, w, kh, kw, stride, mb, itemsize, po, pooled=True)
    return {"pb": pb, "mb": mb, "bb": 1}


def choose_qmatmul_blocks(m: int, n: int, k: int) -> dict[str, int]:
    """int8 MXU-native tiling: sublane×lane = 32×128 for int8 on TPU;
    blocks of at most 128 per dim that divide it (the int8 GEMM does not
    pad) and satisfy the block rule."""
    return legal_qmatmul_tiles(m, n, k, {"bm": 128, "bn": 128, "bk": 128})


def legal_qmatmul_tiles(m: int, n: int, k: int,
                        caps: Mapping[str, int]) -> dict[str, int]:
    """Clamp requested (bm, bn, bk) caps to legal blocks: bm is the int8
    x block's sublane dim, bk its lane dim (and the weight block's
    sublanes), bn the weight/output lane dim."""
    return {"bm": legal_block(m, caps["bm"], INT8_SUBLANE),
            "bn": legal_block(n, caps["bn"], LANE),
            "bk": legal_block(k, caps["bk"], LANE)}


def choose_tree_rows(r: int, cap: int = 256) -> dict[str, int]:
    """Row block for the addition-tree kernel. The wrapper pads R up to a
    multiple of rb and slices, so rb never degenerates to 1 on prime R."""
    return {"rb": min(cap, r)}


def _dtype_name(dtype) -> str:
    try:
        return np.dtype(dtype).name
    except TypeError:           # jax weak types / dtype-like objects
        return str(dtype)


class TuningCache:
    """Measured tile parameters keyed by (op, shape signature, dtype,
    platform). The platform key keeps a cache tuned on TPU from steering
    CPU interpret runs and vice versa — entries only apply where they were
    measured."""

    def __init__(self):
        self._entries: dict[tuple[str, tuple[int, ...], str, str],
                            dict[str, int]] = {}

    @staticmethod
    def key(op: str, shape, dtype, platform: str | None = None
            ) -> tuple[str, tuple[int, ...], str, str]:
        return (op, tuple(int(s) for s in shape), _dtype_name(dtype),
                platform or _platform())

    def get(self, op: str, shape, dtype,
            platform: str | None = None) -> dict[str, int] | None:
        return self._entries.get(self.key(op, shape, dtype, platform))

    def put(self, op: str, shape, dtype, params: Mapping[str, int],
            platform: str | None = None) -> None:
        self._entries[self.key(op, shape, dtype, platform)] = {
            k: int(v) for k, v in dict(params).items()}

    def clear(self) -> None:
        self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)

    def snapshot(self) -> dict:
        """Copy of the entry table (tests save/restore around tuning)."""
        return dict(self._entries)

    def restore(self, entries: dict) -> None:
        self._entries = dict(entries)

    # ---------- row interop (plan artifacts, DESIGN.md §12) ----------
    def export_rows(self) -> list[dict]:
        """Every entry as a JSON-able row (the persisted ``entries``
        shape) — the plan artifact store embeds the rows covering a
        plan's stages in its manifest."""
        return [{"op": op, "shape": list(shape), "dtype": dt,
                 "platform": plat, "params": dict(p)}
                for (op, shape, dt, plat), p in sorted(self._entries.items())]

    def merge_rows(self, rows, *, keep_existing: bool = False,
                   source: str = "tuning rows") -> int:
        """Merge row dicts (``export_rows`` format); returns how many
        landed. ``keep_existing=True`` never overwrites an entry already
        in this process — artifact-embedded rows must not clobber fresher
        local measurements. Malformed rows warn and are skipped."""
        loaded = 0
        for row in rows:
            try:
                key = self.key(row["op"], row["shape"], row["dtype"],
                               row.get("platform"))
                if keep_existing and key in self._entries:
                    continue
                self._entries[key] = {k: int(v)
                                      for k, v in dict(row["params"]).items()}
                loaded += 1
            except (KeyError, TypeError, ValueError):
                warnings.warn(f"{source}: skipping malformed row {row!r}",
                              stacklevel=2)
        return loaded

    # ---------- persistence ----------
    def save(self, path) -> None:
        """Write the versioned JSON cache (schema ``SCHEMA_VERSION``)."""
        doc = {"version": SCHEMA_VERSION, "entries": self.export_rows()}
        pathlib.Path(path).write_text(json.dumps(doc, indent=1) + "\n")

    def load(self, path) -> int:
        """Merge entries from ``path``; returns how many were loaded.

        Robust by design: a corrupt file, an unknown schema version, or
        malformed rows warn and load nothing (heuristics stay in charge)
        rather than raising mid-startup. Only a missing file raises — the
        caller chose the path. The legacy un-versioned list format (PR 2)
        is still accepted; rows without a platform field key under the
        current platform.
        """
        text = pathlib.Path(path).read_text()
        try:
            doc = json.loads(text)
        except json.JSONDecodeError:
            warnings.warn(f"tuning cache {path}: corrupt JSON; falling back "
                          f"to heuristic tiles", stacklevel=2)
            return 0
        legacy = False
        if isinstance(doc, dict):
            if doc.get("version") != SCHEMA_VERSION:
                warnings.warn(
                    f"tuning cache {path}: unknown schema version "
                    f"{doc.get('version')!r} (this build reads "
                    f"{SCHEMA_VERSION}); falling back to heuristic tiles",
                    stacklevel=2)
                return 0
            rows = doc.get("entries", [])
        elif isinstance(doc, list):     # legacy PR-2 format
            rows = doc
            legacy = True
        else:
            warnings.warn(f"tuning cache {path}: expected a JSON object or "
                          f"list, got {type(doc).__name__}; falling back to "
                          f"heuristic tiles", stacklevel=2)
            return 0
        loaded = 0
        for row in rows:
            try:
                op, shape = row["op"], row["shape"]
                if (legacy and op in ("conv2d", "fused_conv_block")
                        and len(shape) != 9):
                    # pre-batch-signature conv entries (PR 2 wrote
                    # 8-element sigs) can never match a lookup now —
                    # don't pretend they loaded
                    warnings.warn(
                        f"tuning cache {path}: skipping stale {op} entry "
                        f"with pre-batch signature {shape} (re-tune to "
                        f"refresh)", stacklevel=2)
                    continue
                self.put(op, shape, row["dtype"], row["params"],
                         platform=row.get("platform"))
                loaded += 1
            except (KeyError, TypeError, ValueError):
                warnings.warn(f"tuning cache {path}: skipping malformed "
                              f"row {row!r}", stacklevel=2)
        return loaded


TUNING_CACHE = TuningCache()


def tile_params(op: str, shape, dtype, defaults: Mapping[str, int],
                overrides: Mapping[str, int] | None = None) -> dict[str, int]:
    """Resolve tile parameters for one op call.

    ``defaults`` come from the analytic heuristic; a tuning-cache entry for
    (op, shape, dtype, platform) refines them; ``overrides``
    (ExecPolicy.tiling) win outright. Override keys may be namespaced
    ``"<op>.<key>"`` to target a single op family; bare keys apply to any
    op that understands them. Unknown keys are ignored so one policy can
    carry tiles for several ops.
    """
    merged = dict(defaults)
    hit = TUNING_CACHE.get(op, shape, dtype)
    if hit:
        merged.update({k: v for k, v in hit.items() if k in defaults})
    ov = dict(overrides or {})
    for k, v in ov.items():             # bare keys first …
        if "." not in k and k in defaults:
            merged[k] = int(v)
    for k, v in ov.items():             # … then namespaced ones win
        name = k.split(".", 1)
        if len(name) == 2 and name[0] == op and name[1] in defaults:
            merged[name[1]] = int(v)
    return merged


_env_cache = os.environ.get("REPRO_TUNING_CACHE")
if _env_cache and os.path.exists(_env_cache):
    TUNING_CACHE.load(_env_cache)
