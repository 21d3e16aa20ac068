"""Block-size autotuning for the Pallas decode kernels.

Every Pallas stage runs over a lane/unit/MCU grid whose tile size is a
free parameter: the Huffman exits kernel (lane tile), the write pass
(smaller lane tile — it carries the ``(TILE, s_max)`` streams), the IDCT
unit tile, and the fused pixel kernel's MCU tile. The historical
constants (``TILE_C``/``WRITE_TILE_C``/``TILE_U``) are good CPU/interpret
defaults but not necessarily optimal per device, so this module provides
a small measured search over a fixed candidate set, keyed by
``(PlanShape, backend, fuse, device_kind)``:

* resolution order: ``REPRO_PALLAS_TILES`` env override (parsed and
  validated loudly) > in-memory cache > persistent on-disk table (only
  when ``REPRO_PALLAS_TILE_TABLE`` names one: a table the caller did not
  name would change which program compiles) > measured search (only when
  a ``measure`` callable is supplied — the decoder wires one up under
  ``REPRO_PALLAS_AUTOTUNE=1``) > the built-in defaults.

* the chosen :class:`TileConfig` is **part of the compiled-program cache
  key** (``core/api.decode_program``), so tuning happens at most once per
  bucket and a warm bucket never re-tunes or retraces: the same config
  resolves from cache and hits the same jitted program.

* every candidate — not just the winner — is covered by the kernel
  memory-safety verifier (``python -m repro.analysis kernels`` traces the
  tier-0 cells at each candidate tile), so a bad tile choice is a CI
  failure, not silent truncation.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from typing import Callable, Dict, List, Optional

TILES_ENV = "REPRO_PALLAS_TILES"
AUTOTUNE_ENV = "REPRO_PALLAS_AUTOTUNE"
TABLE_ENV = "REPRO_PALLAS_TILE_TABLE"

#: Hard cap on any lane/unit tile — far above any plausible VMEM-fitting
#: tile; an override beyond it is a typo, not a tuning decision.
MAX_TILE = 8192


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One point in the block-size search space (hashable: it rides in
    the ``decode_program`` cache key)."""

    exits_tile: int = 1024   # Huffman exits kernel lane tile (TILE_C)
    write_tile: int = 256    # write-pass lane tile (WRITE_TILE_C)
    unit_tile: int = 512     # IDCT kernel unit tile (TILE_U)
    mcu_tile: int = 64       # fused pixel kernel MCUs per grid step

    def label(self) -> str:
        return (f"e{self.exits_tile}:w{self.write_tile}"
                f":u{self.unit_tile}:m{self.mcu_tile}")


DEFAULT_TILES = TileConfig()

#: Per-knob candidate values. The search varies one knob at a time from
#: the default (the knobs bound independent kernels, so the space is a
#: star, not a cross product — a handful of measurements per bucket).
TILE_CANDIDATES: Dict[str, tuple] = {
    "exits_tile": (256, 512, 1024),
    "write_tile": (64, 128, 256),
    "unit_tile": (256, 512),
    "mcu_tile": (16, 32, 64),
}

_FIELD_ALIASES = {
    "exits": "exits_tile", "exits_tile": "exits_tile",
    "write": "write_tile", "write_tile": "write_tile",
    "unit": "unit_tile", "unit_tile": "unit_tile", "idct": "unit_tile",
    "mcu": "mcu_tile", "mcu_tile": "mcu_tile",
}


def check_tile(name: str, value: int) -> int:
    """Loud validation of one tile knob (the parse-time half of the
    kernel-tiling contract; ``huffman._check_lane_tiling`` and the fused
    wrappers' guards are the runtime twins)."""
    if not isinstance(value, int) or isinstance(value, bool):
        raise ValueError(f"tile {name} must be an int, got {value!r}")
    if value <= 0 or value > MAX_TILE:
        raise ValueError(
            f"tile {name}={value} out of range (1..{MAX_TILE})")
    if name in ("exits_tile", "write_tile", "unit_tile") and value % 8:
        raise ValueError(
            f"tile {name}={value} must be a multiple of 8 (sublane "
            f"alignment; a non-multiple would leave the padded lane "
            f"capacity non-divisible by the tile)")
    return value


def candidate_configs(base: TileConfig = DEFAULT_TILES) -> List[TileConfig]:
    """The measured-search candidate set: the base config plus every
    single-knob variation. Deduplicated, base first."""
    out = [base]
    for field, values in TILE_CANDIDATES.items():
        for v in values:
            cand = dataclasses.replace(base, **{field: v})
            if cand not in out:
                out.append(cand)
    return out


def parse_tile_override(text: str) -> TileConfig:
    """Parse ``REPRO_PALLAS_TILES``: ``"exits=512,write=128,mcu=32"``
    (unnamed knobs keep their defaults). Junk raises with the accepted
    grammar — a silently ignored override is a mistuned production fleet.
    """
    fields: Dict[str, int] = {}
    for part in filter(None, (p.strip() for p in text.split(","))):
        if "=" not in part:
            raise ValueError(
                f"{TILES_ENV} entry {part!r} is not key=value; expected "
                f"e.g. 'exits=512,write=128,unit=512,mcu=32'")
        key, _, val = part.partition("=")
        name = _FIELD_ALIASES.get(key.strip())
        if name is None:
            raise ValueError(
                f"{TILES_ENV} key {key.strip()!r} unknown; expected one "
                f"of {sorted(set(_FIELD_ALIASES))}")
        try:
            ival = int(val)
        except ValueError:
            raise ValueError(
                f"{TILES_ENV} value {val!r} for {name} is not an int"
            ) from None
        fields[name] = check_tile(name, ival)
    return dataclasses.replace(DEFAULT_TILES, **fields)


# ---------------------------------------------------------------------------
# Tuned-config cache: in-memory + persistent table
# ---------------------------------------------------------------------------

_TUNED: Dict[str, TileConfig] = {}


def device_kind() -> str:
    """The tuning key's hardware; a backend that fails to start raises."""
    import jax
    return jax.devices()[0].device_kind.replace(" ", "-")


def tune_key(shape, backend: str, fuse: str,
             kind: Optional[str] = None) -> str:
    """The autotune-table key: one entry per (bucket, backend, fuse,
    device kind) — exactly the granularity of the compiled-program cache
    plus the hardware the measurement ran on."""
    label = shape.label() if hasattr(shape, "label") else str(shape)
    return f"{label}|{backend}|{fuse}|{kind or device_kind()}"


def table_path() -> Optional[str]:
    """The persistent tile table ``REPRO_PALLAS_TILE_TABLE`` names, or
    None: no table is read or written unless one is named."""
    return os.environ.get(TABLE_ENV) or None


def _load_table(path: str) -> Dict[str, Dict]:
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, ValueError):
        return {}


def _store_entry(path: str, key: str, cfg: TileConfig) -> None:
    """Best-effort persistent record (read-merge-atomic-replace); a
    read-only filesystem degrades to in-memory-only tuning, never an
    error on the decode path."""
    try:
        table = _load_table(path)
        table[key] = dataclasses.asdict(cfg)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                   prefix=".pallas_tiles.")
        with os.fdopen(fd, "w") as f:
            json.dump(table, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


def clear_tile_cache() -> None:
    """Drop the in-memory tuned-config cache (tests)."""
    _TUNED.clear()


def autotune_enabled() -> bool:
    return os.environ.get(AUTOTUNE_ENV) == "1"


def autotune_tiles(shape, backend: str, fuse: str, *,
                   measure: Optional[Callable[[TileConfig], float]] = None,
                   kind: Optional[str] = None) -> TileConfig:
    """Resolve the tile config for one program bucket.

    ``measure(cfg) -> seconds`` runs one warm decode step under ``cfg``;
    when supplied, the search measures every :func:`candidate_configs`
    point once, memoizes the winner in-process, and persists it to the
    named on-disk table (if any) so future processes skip the search. Without
    ``measure`` the call is pure lookup (override > caches > defaults) —
    it never traces, so resolving tiles for a warm bucket is free.
    """
    override = os.environ.get(TILES_ENV)
    if override:
        return parse_tile_override(override)
    if backend != "pallas":
        return DEFAULT_TILES
    key = tune_key(shape, backend, fuse, kind)
    hit = _TUNED.get(key)
    if hit is not None:
        return hit
    path = table_path()
    row = _load_table(path).get(key) if path else None
    if row is not None:
        try:
            cfg = TileConfig(**{k: check_tile(k, int(v))
                                for k, v in row.items()})
            _TUNED[key] = cfg
            return cfg
        except (TypeError, ValueError):
            pass  # stale/corrupt row: fall through to re-tune or default
    if measure is None:
        _TUNED[key] = DEFAULT_TILES
        return DEFAULT_TILES
    best, best_t = DEFAULT_TILES, float("inf")
    for cand in candidate_configs():
        t = float(measure(cand))
        if t < best_t:
            best, best_t = cand, t
    _TUNED[key] = best
    if path:
        _store_entry(path, key, best)
    return best
