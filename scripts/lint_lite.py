"""AST-based lint gate for environments without ruff.

CI runs the real pinned ruff/mypy as BLOCKING jobs (.github/workflows/
ci.yml — reference parity with clippy --deny warnings,
/root/reference/.github/workflows/ci.yml:33-40).  This module enforces
the deterministic core of that ruleset locally (the dev image carries no
linter), so the committed baseline stays clean between CI runs:

* F401  unused import (module scope; honours __all__ and redundant
        ``import x as x`` re-export aliases)
* F541  f-string without placeholders
* E711  comparison to None with ==/!=
* E712  comparison to True/False with ==/!=
* E722  bare ``except:``
* B006  mutable default argument
* F632  ``is`` comparison with a literal
* DKG001  (dkg_tpu/net/ only) serde ``decode_phase*`` called outside the
        ``_decode_quarantined`` quarantine — malformed peer bytes must
        degrade to silent disqualification, never raise through the
        party driver (docs/fault_model.md)
* DKG002  (dkg_tpu/dkg/ only) fixed-base table built in protocol code
        (``fixed_base_table`` / ``fixed_base_table_dev`` /
        ``_fixed_table_np``) — generator/Pedersen tables must come from
        ``groups.precompute`` (``generator_table``/``base_table``) so
        the persistent cache actually covers every hot path
        (docs/perf.md)
* DKG003  (dkg_tpu/dkg/ batch hot modules only) per-pair DEM primitive
        in a hot path: ``group.encode(...)`` or ``chacha20_xor(...)``
        called outside the scalar reference legs — the dealing pipeline
        must use ``groups.device.encode_batch`` /
        ``crypto.chacha.chacha20_xor_batch`` so n^2 pairs cost one
        vectorized pass, not n^2 host calls (docs/perf.md)
* DKG004  (dkg_tpu/dkg/ only) eager transcript-digest entry point
        (``blake2s_level`` / ``_tree_from_words``) called from protocol
        code — digests must go through ``device_hash.row_digests`` /
        ``tree_digest`` so every call is jitted and backend-dispatched
        (DKG_TPU_DIGEST); and, in the batch hot modules, a
        ``hashlib.blake2b`` call lexically inside a loop — a per-pair
        or per-dealer hash loop of long messages is what ``crypto.
        blake2.blake2b_batch`` exists for.  Allowed where it was
        measured or is the oracle: ``fiat_shamir_rho`` (36-byte lanes:
        the C library beat the numpy batch at every lane count to 4096;
        PERF.md section 6, PR 37) and the audit leg
        ``_dealer_row_digests`` (docs/perf.md)
* DKG005  (dkg_tpu/net/ only, net/checkpoint.py exempt) raw file write —
        write-mode ``open()``, ``.write_bytes``/``.write_text``, or
        fd-level ``os.open`` — outside the WAL: net-layer state carries
        secret share material and must be persisted through
        ``net.checkpoint.PartyWal`` only (0600, fsync'd, checksummed,
        torn-tail tolerant; docs/fault_model.md "Crash recovery")
* DKG006  (dkg_tpu/ only; scripts/tests exempt) ad-hoc telemetry: a bare
        ``print()`` call, or a raw file write outside the sanctioned
        writers (utils/obslog.py — the flight-recorder sink,
        groups/precompute.py — the table cache, and dkg_tpu/net/ which
        DKG005 already polices) — library telemetry goes through
        ``utils.obslog`` / ``utils.metrics`` so events are structured,
        redacted, and capturable (docs/observability.md)
* DKG007  (dkg_tpu/service/ only) configuration or concurrency taken
        outside the sanctioned owners: a raw ``os.environ`` read or
        ``os.getenv()`` call — every service knob goes through
        ``utils.envknobs`` so a typo'd value fails loudly with the
        knob's name and meaning — or a bare thread/process spawn
        (``threading.Thread``, ``ThreadPoolExecutor``, ``Process``, …)
        outside the sanctioned owners (``scheduler.py``'s worker pool,
        ``httpobs.py``'s scrape-server thread), so concurrency has few
        auditable owners (docs/service.md)
* DKG008  (dkg_tpu/epoch/ only) per-pair EC scalar work or ad-hoc
        persistence in epoch code: a ``scalar_mul``/
        ``scalar_mul_vartime`` call lexically inside a loop — epoch
        dealing/verification must go through the batched ceremony
        entry points (``deal_chunked``, ``open_shares_batch``,
        ``gd.fixed_base_mul``/``gd.eval_point_poly``/``gd.scalar_mul``
        over stacked rows; epoch/dealing.py) so refresh cost scales
        like the ceremony, not like n^2 host mults — or a raw file
        write: epoch state (it contains shares) persists ONLY through
        the party WAL (``net.checkpoint.PartyWal`` epoch records;
        docs/resharing.md)
* DKG009  (dkg_tpu/sign/ only) per-message scalar work or raw
        configuration in signing code: a ``scalar_mul``/
        ``scalar_mul_vartime`` call lexically inside a loop — partial
        signing and aggregation must run as ONE batched device call
        (broadcast ladder / Pippenger MSM) so B messages x t+1 signers
        cost one dispatch, not B·(t+1) host mults; the ``*_host``
        big-int oracle legs the device paths are pinned against are
        allowlisted by name suffix — or a raw ``os.environ`` /
        ``os.getenv`` read: signing knobs (DKG_TPU_SIGN_*) go through
        ``utils.envknobs`` (docs/signing.md)
* DKG010  (dkg_tpu/service/ and dkg_tpu/sign/ only) silent failure
        handling on the serving path: an ``except Exception`` handler
        whose body neither re-raises nor records the failure (a metric
        ``inc``/``observe``/``set_gauge``, an obslog ``emit*``, or one
        of the scheduler's containment entry points — see
        ``_DKG010_RECORDERS``) swallows a fault the blast-radius
        machinery exists to account for; and a literal
        ``raise RuntimeError`` — failures there must use the typed
        taxonomy in ``service/errors.py`` (PoisonedRequest,
        TransientEngineError, …) so callers and the isolation logic can
        branch on type, never on message text (docs/fault_model.md
        "Service fault model")
* DKG011  (dkg_tpu/ only) undocumented metric name: every literal
        metric name emitted via ``.inc(...)`` / ``.observe(...)`` /
        ``.set_gauge(...)`` in library code must appear in
        ``docs/observability.md``'s metric reference, so the scrape
        surface (``/metrics``, bench snapshots) cannot silently drift
        from its documentation (allowlist:
        ``_DKG011_UNDOCUMENTED_OK``)
* DKG012  (dkg_tpu/net/ only, net/checkpoint.py exempt) raw socket I/O
        — ``.sendall(...)`` / ``.send(...)`` / ``.recv(...)`` /
        ``.recv_into(...)`` — outside the counted wire helpers
        (``_wire_send`` and ``_CountedReader`` in net/channel.py):
        every transport byte must flow through them so the
        ``net_wire_bytes_total{dir,op}`` accounting stays exact
        (docs/observability.md, "Wire accounting")
* DKG013  (dkg_tpu/service/ only) per-request re-derivation of
        quorum-stable signing material: a ``lagrange_at_zero_coeffs`` /
        ``lagrange_coefficient`` / ``public_keys`` call — the sign
        lane's hot path must take Lagrange coefficients, pk ladders,
        and decoded shares from ``sign.cache.SignCache`` (cached per
        (curve, quorum) / (ceremony, epoch)), because SIGN_r01 measured
        exactly this re-derivation dominating steady-state signing
        (docs/signing.md "Steady-state lane")
* DKG014  (dkg_tpu/ only, dkg_tpu/ops/ exempt) ``pallas_call`` outside
        the kernel layer: every Pallas program lives in ``dkg_tpu/ops/``
        behind its dispatch seam (``fused_kernels_active`` and the
        interpret/Mosaic fallbacks), so a kernel launched from protocol
        or group code would bypass the backend gating, the
        ``pallas_calls_total`` accounting, and the bit-exactness test
        tiers (docs/perf.md "MXU formulation")
* DKG015  (dkg_tpu/ only, dkg_tpu/parallel/ exempt) mesh machinery
        constructed outside the parallel layer: a ``Mesh`` /
        ``PartitionSpec`` / ``NamedSharding`` construction or a
        ``shard_map`` call — and the jax imports that provide them —
        anywhere else in the library.  Sharding topology has exactly
        one owner (``parallel/mesh.py``'s PARTY_AXIS convention, its
        ``_shard_map_nocheck`` version seam, ``parallel/signmesh.py``'s
        sign-lane mesh); call sites take a mesh HANDLE
        (``make_mesh``/``sign_mesh``) so axis names, check-kwarg
        compatibility, and placement policy cannot fork per module
        (docs/perf.md "Sharded ceremony")
* DKG016  (dkg_tpu/service/fleet.py only) any ``jax`` import: the fleet
        control plane is device-free by design — a ``jax.jit`` tracing
        entry point in the front door's request path would recreate the
        per-process cold start the AOT store exists to kill, and would
        initialize a JAX runtime in the parent that every spawned
        worker then re-initializes.  Executables live in workers
        (service/engine.py dispatch seams, service/aot.py store); the
        parent routes bytes
* DKG017  (dkg_tpu/service/fleet.py only) ``_placed`` entries removed
        outside the sanctioned eviction/manifest helpers
        (``_evict_placed`` / ``_adopt_manifest`` / ``_tombstone_slot``
        / ``close``): a ``del`` / ``.pop`` / ``.clear`` anywhere else
        is a silent placement drop — exactly the bug the failover work
        removed, where a reaped worker's accepted ceremonies vanished
        (poll -> "unknown") instead of becoming orphans the slot
        journal can resurrect or tombstones that explain themselves

Exit 0 = clean.  Run: ``python scripts/lint_lite.py`` (from repo root).
Also executed by tests/test_import_hygiene.py so the default test tier
blocks on regressions exactly like CI does.
"""

from __future__ import annotations

import ast
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
TARGETS = ["dkg_tpu", "tests", "examples", "scripts", "bench.py", "__graft_entry__.py"]


def _iter_files() -> list[pathlib.Path]:
    out: list[pathlib.Path] = []
    for t in TARGETS:
        p = REPO / t
        if p.is_file():
            out.append(p)
        else:
            out.extend(sorted(p.rglob("*.py")))
    return out


# Functions allowed to call serde.decode_phase* inside dkg_tpu/net/
# (the DKG001 quarantine boundary, net/party.py).
_DECODE_QUARANTINES = {"_decode_quarantined"}

# The fixed-base table builders protocol code (dkg_tpu/dkg/) must not
# call directly (DKG002): going around groups/precompute.py rebuilds
# generator/Pedersen tables from scratch every process and silently
# forfeits the persistent cache.  Variable-point helpers (_build_table
# on per-verify commitment points) are NOT in this set — only the
# fixed-base family has a precomputed identity worth persisting.
_FIXED_TABLE_BUILDERS = {
    "fixed_base_table",
    "fixed_base_table_dev",
    "_fixed_table_np",
}

# Batch hot modules under dkg_tpu/dkg/ where per-pair DEM primitives are
# banned (DKG003): these run once per (dealer, recipient) pair, so a
# scalar group.encode or chacha20_xor inside them is an O(n^2) host loop
# the vectorized pipeline exists to eliminate.
_DEM_HOT_MODULES = {
    "hybrid_batch.py",
    "committee_batch.py",
    "complaints_batch.py",
    "ceremony.py",
}

# Functions inside hot modules allowed to use scalar DEM primitives:
# the scalar reference legs (DKG_TPU_DEM=scalar) that the byte-identity
# tests diff the batch path against.
_DEM_SCALAR_LEGS = {"seal_shares", "open_share"}

# Eager transcript-digest entry points protocol code must not call
# directly (DKG004): the public ``row_digests``/``tree_digest``
# dispatchers are jitted and backend-dispatched (DKG_TPU_DIGEST); these
# internals are neither.
_DIGEST_EAGER_ENTRYPOINTS = {"blake2s_level", "_tree_from_words"}

# Functions inside hot modules allowed to run hashlib.blake2b in a
# loop (DKG004): the byte-level audit digest's per-dealer row hash —
# the oracle the vectorized paths are diffed against — and rho's lanes,
# 36-byte messages that the C library hashes faster than
# blake2b_batch's array operations at every lane count (PR 37).
_DIGEST_HOST_LEGS = {"_dealer_row_digests", "fiat_shamir_rho"}

# Library modules sanctioned to write files directly (DKG006):
# the flight-recorder JSONL sink and the persistent table cache.
# dkg_tpu/net/ is excluded from DKG006's write check because DKG005
# already polices it more strictly (WAL-only).
_DKG006_WRITER_ALLOWLIST = {"obslog.py", "precompute.py", "aot.py"}

# Execution-context constructors banned in dkg_tpu/service/ outside the
# sanctioned owners (DKG007): the worker pool in scheduler.py and the
# scrape-server thread in httpobs.py.
_SERVICE_SPAWNERS = {
    "Thread",
    "ThreadPoolExecutor",
    "ProcessPoolExecutor",
    "Process",
    "start_new_thread",
    "run_in_executor",
}
_SERVICE_SPAWN_OWNERS = {"scheduler.py", "httpobs.py", "fleet.py"}

# Per-pair EC scalar multiplication entry points banned inside loops in
# dkg_tpu/epoch/ (DKG008): a host scalar_mul per (dealer, recipient)
# pair is the O(n^2) pathology the batched kernels exist to avoid.
# (Batched gd.scalar_mul over stacked rows sits OUTSIDE any loop.)
_EPOCH_SCALAR_MULS = {"scalar_mul", "scalar_mul_vartime"}

# Calls that count as "recording the failure" inside an
# ``except Exception`` handler on the serving path (DKG010): metric
# writes, flight-recorder emits, and the scheduler's containment entry
# points (each of which metrics+emits internally).  A handler that does
# none of these and does not re-raise is swallowing a fault silently.
_DKG010_RECORDERS = {
    "inc",
    "observe",
    "set_gauge",
    "emit",
    "emit_current",
    "emit_span",
    "_emit",
    "_isolate",
    "_isolate_sign",
    "_fail_convoy",
    "_poison_one",
    "_poison_sign_one",
    "_retry_transient",
    "_note",
    "note_error",
    "record_done",
    "_finish_one",
}

# Registry write methods whose literal first argument is a metric name
# (DKG011): every such name in dkg_tpu/ must appear in
# docs/observability.md's metric reference.
_DKG011_EMITTERS = {"inc", "observe", "set_gauge"}

# Metric names exempt from the DKG011 docs requirement (test-only or
# deliberately undocumented names; currently none).
_DKG011_UNDOCUMENTED_OK: set[str] = set()

# The only functions allowed to remove FleetServer._placed entries
# (DKG017): reap-eviction, manifest adoption, quarantine tombstoning,
# and shutdown.  Everything else may only read or add placements.
_PLACED_MUTATORS = {
    "_evict_placed",
    "_adopt_manifest",
    "_tombstone_slot",
    "close",
}

# Mapping methods that remove entries (DKG017's call spelling).
_PLACED_REMOVERS = {"pop", "clear", "popitem"}

# Raw socket I/O methods banned in dkg_tpu/net/ outside the counted
# wire helpers (DKG012): bytes that bypass them are invisible to
# net_wire_bytes_total, so the per-ceremony wire totals and the
# perf_regress wire gate would silently under-count.
_RAW_SOCKET_IO = {"sendall", "send", "recv", "recv_into"}

# Functions sanctioned to touch sockets directly (DKG012): the counted
# send helper and the counting reader wrapper in net/channel.py.
_DKG012_WIRE_HELPERS = {"_wire_send", "_CountedReader"}

# The same entry points banned inside loops in dkg_tpu/sign/ (DKG009):
# a host scalar_mul per (message, signer) pair is the B·(t+1) pathology
# the broadcast ladder and the batched MSM exist to avoid.  Functions
# whose name ends in ``_host`` are the allowlisted big-int oracle legs
# (bit-exactness references, never hot paths).
_SIGN_HOST_ORACLE_SUFFIX = "_host"

# Quorum-stable derivations banned in dkg_tpu/service/ (DKG013): the
# sign lane must take this material from sign.cache.SignCache — calling
# these per request is the re-derivation SIGN_r01 measured dominating
# the steady state.  (sign/cache.py itself, in dkg_tpu/sign/, is the
# one sanctioned caller.)
_DKG013_CACHED_DERIVATIONS = {
    "lagrange_at_zero_coeffs",
    "lagrange_coefficient",
    "public_keys",
}

# Mesh machinery banned outside dkg_tpu/parallel/ (DKG015): sharding
# topology (axis names, PartitionSpecs, the shard_map version seam)
# has exactly one owner; everyone else takes a mesh handle.
_DKG015_MESH_MACHINERY = {
    "Mesh",
    "PartitionSpec",
    "NamedSharding",
    "shard_map",
}


class _Checker(ast.NodeVisitor):
    def __init__(self, path: pathlib.Path, tree: ast.Module, source: str):
        self.path = path
        self.problems: list[tuple[int, str, str]] = []
        self.metric_names: list[tuple[int, str]] = []  # DKG011 emissions
        self.used_names: set[str] = set()
        self.imports: list[tuple[int, str, str, bool]] = []  # line, local, code, reexport
        self.dunder_all: set[str] = set()
        self._source_lines = source.splitlines()
        self._func_stack: list[str] = []
        self._loop_depth = 0
        self._net_module = "dkg_tpu/net/" in path.as_posix()
        self._dkg_module = "dkg_tpu/dkg/" in path.as_posix()
        self._pkg_module = "dkg_tpu/" in path.as_posix()
        self._service_module = "dkg_tpu/service/" in path.as_posix()
        self._ops_module = "dkg_tpu/ops/" in path.as_posix()
        self._epoch_module = "dkg_tpu/epoch/" in path.as_posix()
        self._sign_module = "dkg_tpu/sign/" in path.as_posix()
        self._parallel_module = "dkg_tpu/parallel/" in path.as_posix()
        self._fleet_module = self._service_module and path.name == "fleet.py"
        self._dem_hot_module = (
            self._dkg_module and path.name in _DEM_HOT_MODULES
        )
        self._collect_all(tree)
        self.visit(tree)

    def _noqa(self, line: int) -> bool:
        idx = line - 1
        return 0 <= idx < len(self._source_lines) and "noqa" in self._source_lines[idx]

    def _add(self, node: ast.AST, code: str, msg: str) -> None:
        line = getattr(node, "lineno", 0)
        if not self._noqa(line):
            self.problems.append((line, code, msg))

    def _collect_all(self, tree: ast.Module) -> None:
        for node in tree.body:
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) else [node.target]
                if any(isinstance(t, ast.Name) and t.id == "__all__" for t in targets):
                    val = node.value
                    if isinstance(val, (ast.List, ast.Tuple)):
                        for elt in val.elts:
                            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                                self.dunder_all.add(elt.value)

    # -- name usage ----------------------------------------------------
    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self.used_names.add(node.id)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        # DKG007a: raw environment access in service code — every knob
        # must go through utils.envknobs (validated, named, documented).
        if (
            self._service_module
            and node.attr == "environ"
            and isinstance(node.value, ast.Name)
            and node.value.id == "os"
        ):
            self._add(
                node,
                "DKG007",
                "os.environ in dkg_tpu/service/ — read knobs through "
                "utils.envknobs so bad values fail loudly and every knob "
                "is documented",
            )
        # DKG009a: same ownership rule for signing code — DKG_TPU_SIGN_*
        # knobs are validated and documented in utils.envknobs.
        if (
            self._sign_module
            and node.attr == "environ"
            and isinstance(node.value, ast.Name)
            and node.value.id == "os"
        ):
            self._add(
                node,
                "DKG009",
                "os.environ in dkg_tpu/sign/ — read knobs through "
                "utils.envknobs so bad values fail loudly and every knob "
                "is documented",
            )
        self.generic_visit(node)

    # -- imports -------------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            local = (alias.asname or alias.name).split(".")[0]
            reexport = alias.asname is not None and alias.asname == alias.name
            self.imports.append((node.lineno, local, "F401", reexport))
            # DKG016: the fleet control plane never touches jax — at any
            # nesting depth (a function-level import is still a tracing
            # entry point waiting to happen on the request path)
            if self._fleet_module and alias.name.split(".")[0] == "jax":
                self._add(
                    node,
                    "DKG016",
                    "jax imported in service/fleet.py — the fleet front "
                    "door is device-free; executables live in worker "
                    "processes behind the AOT store (service/aot.py)",
                )
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        for alias in node.names:
            if alias.name == "*":
                continue
            local = alias.asname or alias.name
            reexport = alias.asname is not None and alias.asname == alias.name
            self.imports.append((node.lineno, local, "F401", reexport))
            # DKG016 (from-import spelling): see visit_Import
            if (
                self._fleet_module
                and node.module
                and node.module.split(".")[0] == "jax"
            ):
                self._add(
                    node,
                    "DKG016",
                    "jax imported in service/fleet.py — the fleet front "
                    "door is device-free; executables live in worker "
                    "processes behind the AOT store (service/aot.py)",
                )
            # DKG015a: importing mesh machinery from jax outside the
            # parallel layer — aliasing (``PartitionSpec as P``) is the
            # common spelling, so the import is where the rule bites.
            if (
                self._pkg_module
                and not self._parallel_module
                and node.module
                and node.module.split(".")[0] == "jax"
                and alias.name in _DKG015_MESH_MACHINERY
            ):
                self._add(
                    node,
                    "DKG015",
                    f"{alias.name} imported from {node.module} outside "
                    "dkg_tpu/parallel/ — sharding topology has one owner; "
                    "take a mesh handle (parallel.mesh.make_mesh / "
                    "parallel.signmesh.sign_mesh) instead",
                )
        self.generic_visit(node)

    # -- rules ---------------------------------------------------------
    def visit_Compare(self, node: ast.Compare) -> None:
        for op, comp in zip(node.ops, node.comparators):
            if isinstance(op, (ast.Eq, ast.NotEq)):
                if isinstance(comp, ast.Constant) and comp.value is None:
                    self._add(node, "E711", "comparison to None with ==/!=; use is")
                elif isinstance(comp, ast.Constant) and isinstance(comp.value, bool):
                    self._add(node, "E712", "comparison to True/False with ==/!=")
            if isinstance(op, (ast.Is, ast.IsNot)):
                if isinstance(comp, ast.Constant) and not isinstance(
                    comp.value, (bool, type(None), type(...))
                ):
                    self._add(node, "F632", "is comparison with a literal")
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if node.type is None:
            self._add(node, "E722", "bare except")
        # DKG010a: serving-path code may catch Exception ONLY to
        # account for it — the handler body must re-raise or hit a
        # recorder (metric / obslog / containment entry point) so no
        # fault disappears without a metric and an event.
        if (
            (self._service_module or self._sign_module)
            and isinstance(node.type, ast.Name)
            and node.type.id == "Exception"
        ):
            recorded = False
            for sub in node.body:
                for inner in ast.walk(sub):
                    if isinstance(inner, ast.Raise):
                        recorded = True
                    elif isinstance(inner, ast.Call):
                        f = inner.func
                        fname = f.attr if isinstance(f, ast.Attribute) else (
                            f.id if isinstance(f, ast.Name) else ""
                        )
                        if fname in _DKG010_RECORDERS:
                            recorded = True
            if not recorded:
                self._add(
                    node,
                    "DKG010",
                    "except Exception swallowed without recording in "
                    "dkg_tpu/service|sign/ — re-raise or record the "
                    "failure (metrics.inc / obslog emit / a containment "
                    "entry point) before continuing",
                )
        self.generic_visit(node)

    def visit_Raise(self, node: ast.Raise) -> None:
        # DKG010b: the serving path's failure taxonomy is typed
        # (service/errors.py) — a bare RuntimeError gives the isolation
        # machinery and callers nothing to branch on.
        if self._service_module or self._sign_module:
            exc = node.exc
            name = ""
            if isinstance(exc, ast.Call) and isinstance(exc.func, ast.Name):
                name = exc.func.id
            elif isinstance(exc, ast.Name):
                name = exc.id
            if name == "RuntimeError":
                self._add(
                    node,
                    "DKG010",
                    "raise RuntimeError in dkg_tpu/service|sign/ — raise a "
                    "typed error from service/errors.py instead "
                    "(PoisonedRequest, TransientEngineError, "
                    "InsufficientSigners, …)",
                )
        self.generic_visit(node)

    def visit_JoinedStr(self, node: ast.JoinedStr) -> None:
        if not any(isinstance(v, ast.FormattedValue) for v in node.values):
            self._add(node, "F541", "f-string without placeholders")
        # visit interpolated expressions (and any dynamic format specs,
        # which can use names) — but not the spec JoinedStr itself: a
        # format spec ("{x:8.3f}") must not be treated as an f-string
        for v in node.values:
            if isinstance(v, ast.FormattedValue):
                self.visit(v.value)
                if v.format_spec is not None:
                    for sub in v.format_spec.values:
                        if isinstance(sub, ast.FormattedValue):
                            self.visit(sub.value)

    def _check_defaults(self, node: ast.FunctionDef | ast.AsyncFunctionDef) -> None:
        for default in list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]:
            if isinstance(default, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(default, ast.Call)
                and isinstance(default.func, ast.Name)
                and default.func.id in ("list", "dict", "set")
            ):
                self._add(default, "B006", f"mutable default argument in {node.name}()")

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_defaults(node)
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._check_defaults(node)
        self._func_stack.append(node.name)
        self.generic_visit(node)
        self._func_stack.pop()

    def _visit_loop(self, node: ast.AST) -> None:
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    # loop tracking for DKG004: comprehensions count — a blake2b in a
    # listcomp is the same per-dealer host loop spelled differently
    visit_For = _visit_loop
    visit_AsyncFor = _visit_loop
    visit_While = _visit_loop
    visit_ListComp = _visit_loop
    visit_SetComp = _visit_loop
    visit_DictComp = _visit_loop
    visit_GeneratorExp = _visit_loop

    def _raw_write_name(self, node: ast.Call) -> str:
        """The called name when ``node`` is a raw file write —
        write-mode ``open()``, ``.write_bytes``/``.write_text``, or
        fd-level ``os.open`` — else "" (shared by DKG005/DKG006)."""
        func = node.func
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else ""
        )
        raw_write = name in ("write_bytes", "write_text")
        if not raw_write and name == "open":
            if isinstance(func, ast.Attribute):
                recv = func.value
                # fd-level os.open: a hand-rolled persistence path
                raw_write = isinstance(recv, ast.Name) and recv.id == "os"
            else:
                mode = node.args[1] if len(node.args) >= 2 else None
                for kw in node.keywords:
                    if kw.arg == "mode":
                        mode = kw.value
                raw_write = (
                    isinstance(mode, ast.Constant)
                    and isinstance(mode.value, str)
                    and any(c in mode.value for c in "wax+")
                )
        return name if raw_write else ""

    @staticmethod
    def _is_self_placed(node: ast.AST) -> bool:
        """True for the ``self._placed`` attribute expression."""
        return (
            isinstance(node, ast.Attribute)
            and node.attr == "_placed"
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
        )

    def visit_Delete(self, node: ast.Delete) -> None:
        # DKG017 (del spelling): ``del self._placed[cid]`` outside the
        # sanctioned placement-removal helpers is a silent drop.
        if self._fleet_module and not (set(self._func_stack) & _PLACED_MUTATORS):
            for tgt in node.targets:
                if isinstance(tgt, ast.Subscript) and self._is_self_placed(
                    tgt.value
                ):
                    self._add(
                        node,
                        "DKG017",
                        "del self._placed[...] outside the sanctioned "
                        "helpers (_evict_placed/_adopt_manifest/"
                        "_tombstone_slot/close) — placements leave the "
                        "map as orphans, tombstones or evictions, never "
                        "silently",
                    )
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        # DKG017 (method spelling): self._placed.pop()/.clear() outside
        # the sanctioned placement-removal helpers.
        if self._fleet_module and not (set(self._func_stack) & _PLACED_MUTATORS):
            func = node.func
            if (
                isinstance(func, ast.Attribute)
                and func.attr in _PLACED_REMOVERS
                and self._is_self_placed(func.value)
            ):
                self._add(
                    node,
                    "DKG017",
                    f"self._placed.{func.attr}() outside the sanctioned "
                    "helpers (_evict_placed/_adopt_manifest/"
                    "_tombstone_slot/close) — placements leave the map "
                    "as orphans, tombstones or evictions, never silently",
                )
        # DKG001: net-layer decodes must route through the quarantine —
        # a raw decode_phase* call lets Byzantine bytes raise through
        # run_party (malformed messages must disqualify the sender).
        if self._net_module:
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name.startswith("decode_phase") and not (
                set(self._func_stack) & _DECODE_QUARANTINES
            ):
                self._add(
                    node,
                    "DKG001",
                    f"{name}() outside _decode_quarantined — malformed peer "
                    "bytes must quarantine, not raise",
                )
        # DKG002: protocol code must take fixed-base tables from
        # groups.precompute (persistent cache), never build them ad hoc.
        if self._dkg_module:
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name in _FIXED_TABLE_BUILDERS:
                self._add(
                    node,
                    "DKG002",
                    f"{name}() in dkg/ — use groups.precompute."
                    "generator_table/base_table so fixed-base tables hit "
                    "the persistent cache",
                )
        # DKG003: per-pair DEM primitives in batch hot modules — scalar
        # group.encode / chacha20_xor inside the dealing pipeline is an
        # O(n^2) host loop; route through encode_batch / *_xor_batch.
        if self._dem_hot_module and not (set(self._func_stack) & _DEM_SCALAR_LEGS):
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            per_pair = name == "chacha20_xor"
            if not per_pair and name == "encode" and isinstance(func, ast.Attribute):
                # only GROUP encodes: receiver named exactly ``group``
                # (``fh.encode``/``str.encode`` etc. are fine)
                recv = func.value
                per_pair = (
                    isinstance(recv, ast.Name) and recv.id == "group"
                ) or (isinstance(recv, ast.Attribute) and recv.attr == "group")
            if per_pair:
                self._add(
                    node,
                    "DKG003",
                    f"per-pair {name}() in a dkg/ hot path — use "
                    "groups.device.encode_batch / crypto.chacha."
                    "chacha20_xor_batch (scalar legs: seal_shares/"
                    "open_share only)",
                )
        # DKG004a: protocol code must use the jitted, backend-dispatched
        # digest API (row_digests/tree_digest), never the eager
        # device-tree internals.
        if self._dkg_module:
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name in _DIGEST_EAGER_ENTRYPOINTS:
                self._add(
                    node,
                    "DKG004",
                    f"{name}() in dkg/ — use device_hash.row_digests/"
                    "tree_digest so the digest is jitted and "
                    "backend-dispatched (DKG_TPU_DIGEST)",
                )
        # DKG005: net-layer state (WAL records hold secret shares) is
        # persisted ONLY through net.checkpoint.PartyWal — raw writes
        # are not atomic, not fsync'd, not checksummed, and not 0600.
        # checkpoint.py itself is the sanctioned fd-level writer.
        if self._net_module and self.path.name != "checkpoint.py":
            name = self._raw_write_name(node)
            if name:
                self._add(
                    node,
                    "DKG005",
                    f"raw file write ({name}) in dkg_tpu/net/ — persist "
                    "through net.checkpoint.PartyWal (atomic, fsync'd, "
                    "checksummed, 0600)",
                )
        # DKG012: wire accounting is load-bearing (perf gates + SLO
        # layer read net_wire_bytes_total) — every socket send/receive
        # in dkg_tpu/net/ must flow through the counted helpers
        # (_wire_send / _CountedReader) so no byte escapes the meter.
        # checkpoint.py (WAL, fd-level file IO) is out of scope.
        if self._net_module and self.path.name != "checkpoint.py":
            func = node.func
            if (
                isinstance(func, ast.Attribute)
                and func.attr in _RAW_SOCKET_IO
                and not (set(self._func_stack) & _DKG012_WIRE_HELPERS)
            ):
                self._add(
                    node,
                    "DKG012",
                    f"raw socket .{func.attr}() in dkg_tpu/net/ — route "
                    "through the counted wire helpers (_wire_send / "
                    "_CountedReader) so net_wire_bytes_total stays exact",
                )
        # DKG006: no ad-hoc telemetry in library code — a bare print()
        # anywhere in dkg_tpu/, or a raw file write outside the
        # sanctioned writers (net/ is DKG005's stricter domain), must go
        # through utils.obslog / utils.metrics instead.
        if self._pkg_module:
            func = node.func
            if isinstance(func, ast.Name) and func.id == "print":
                self._add(
                    node,
                    "DKG006",
                    "print() in dkg_tpu/ — emit structured events via "
                    "utils.obslog / counters via utils.metrics",
                )
            if (
                not self._net_module
                and self.path.name not in _DKG006_WRITER_ALLOWLIST
            ):
                name = self._raw_write_name(node)
                if name:
                    self._add(
                        node,
                        "DKG006",
                        f"raw file write ({name}) in dkg_tpu/ — telemetry "
                        "goes through utils.obslog (sanctioned writers: "
                        "utils/obslog.py, groups/precompute.py)",
                    )
            # DKG011 collection: literal metric names emitted through a
            # registry write method; run() checks them against the
            # docs/observability.md reference after the file walk
            if (
                isinstance(func, ast.Attribute)
                and func.attr in _DKG011_EMITTERS
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                self.metric_names.append(
                    (node.lineno, node.args[0].value)
                )
        # DKG007b: config/concurrency ownership in service code —
        # os.getenv bypasses envknobs' validation, and any execution
        # context created outside scheduler.py's worker pool splits the
        # concurrency story across files.
        if self._service_module:
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name == "getenv":
                self._add(
                    node,
                    "DKG007",
                    "os.getenv() in dkg_tpu/service/ — read knobs through "
                    "utils.envknobs so bad values fail loudly and every "
                    "knob is documented",
                )
            if (
                name in _SERVICE_SPAWNERS
                and self.path.name not in _SERVICE_SPAWN_OWNERS
            ):
                self._add(
                    node,
                    "DKG007",
                    f"{name}() in dkg_tpu/service/ — the scheduler's "
                    "worker pool (service/scheduler.py) and the scrape "
                    "server (service/httpobs.py) are the only sanctioned "
                    "thread/process spawn sites",
                )
            # DKG013: quorum-stable signing material is cached — a
            # direct Lagrange/pk derivation in service code is the
            # per-request re-derivation the steady-state lane removed.
            if name in _DKG013_CACHED_DERIVATIONS:
                self._add(
                    node,
                    "DKG013",
                    f"{name}() in dkg_tpu/service/ — take Lagrange "
                    "coefficients / pk ladders / decoded shares from "
                    "sign.cache.SignCache (per-request re-derivation is "
                    "the SIGN_r01 steady-state pathology)",
                )
        # DKG008: epoch code must scale like the ceremony — EC scalar
        # mults go through the batched entry points (epoch/dealing.py),
        # never one host scalar_mul per pair in a loop — and epoch state
        # (shares!) persists only through the party WAL.
        if self._epoch_module:
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name in _EPOCH_SCALAR_MULS and self._loop_depth > 0:
                self._add(
                    node,
                    "DKG008",
                    f"{name}() inside a loop in dkg_tpu/epoch/ — use the "
                    "batched dealing/verify entry points (deal_chunked, "
                    "open_shares_batch, gd.fixed_base_mul/eval_point_poly/"
                    "scalar_mul over stacked rows)",
                )
            wname = self._raw_write_name(node)
            if wname:
                self._add(
                    node,
                    "DKG008",
                    f"raw file write ({wname}) in dkg_tpu/epoch/ — epoch "
                    "state persists only through net.checkpoint.PartyWal "
                    "epoch records",
                )
        # DKG009b: signing hot paths must stay batched — one broadcast
        # ladder for all (message, signer) partials, one Pippenger MSM
        # for aggregation.  A scalar_mul inside a loop is the B·(t+1)
        # host pathology; the *_host oracle legs are the one exception.
        # os.getenv likewise bypasses envknobs' validation.
        if self._sign_module:
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name == "getenv":
                self._add(
                    node,
                    "DKG009",
                    "os.getenv() in dkg_tpu/sign/ — read knobs through "
                    "utils.envknobs so bad values fail loudly and every "
                    "knob is documented",
                )
            if (
                name in _EPOCH_SCALAR_MULS
                and self._loop_depth > 0
                and not any(
                    f.endswith(_SIGN_HOST_ORACLE_SUFFIX)
                    for f in self._func_stack
                )
            ):
                self._add(
                    node,
                    "DKG009",
                    f"{name}() inside a loop in dkg_tpu/sign/ — partials "
                    "and aggregation run as ONE batched call "
                    "(gd.scalar_mul over the (B, t+1) grid / "
                    "gd.msm_pippenger); *_host oracle legs only",
                )
        # DKG014: Pallas programs live in dkg_tpu/ops/ only — a
        # pallas_call anywhere else bypasses the fused-tier dispatch
        # seams, the kernel-call accounting, and the parity test tiers.
        if self._pkg_module and not self._ops_module:
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name == "pallas_call":
                self._add(
                    node,
                    "DKG014",
                    "pallas_call outside dkg_tpu/ops/ — kernels live in "
                    "the ops layer behind fused_kernels_active and the "
                    "interpret/Mosaic dispatch seams",
                )
        # DKG015b: mesh machinery constructed outside the parallel
        # layer — a Mesh/PartitionSpec/NamedSharding construction or a
        # shard_map call anywhere else forks the topology ownership
        # (axis names, the check-kwarg version seam, placement policy).
        if self._pkg_module and not self._parallel_module:
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name in _DKG015_MESH_MACHINERY:
                self._add(
                    node,
                    "DKG015",
                    f"{name}() outside dkg_tpu/parallel/ — sharding "
                    "topology has one owner; take a mesh handle "
                    "(parallel.mesh.make_mesh / parallel.signmesh."
                    "sign_mesh) instead",
                )
        # DKG004b: a hashlib.blake2b call lexically inside a loop in a
        # batch hot module, outside the legs where the loop was measured
        # against crypto.blake2.blake2b_batch and won (or is the oracle).
        if (
            self._dem_hot_module
            and self._loop_depth > 0
            and not (set(self._func_stack) & _DIGEST_HOST_LEGS)
        ):
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else ""
            )
            if name == "blake2b":
                self._add(
                    node,
                    "DKG004",
                    "hashlib.blake2b inside a loop in a dkg/ hot module — "
                    "use crypto.blake2.blake2b_batch, or time the loop "
                    "against it first (allowed legs: "
                    + ", ".join(sorted(_DIGEST_HOST_LEGS)) + ")",
                )
        self.generic_visit(node)

    # -- finalize ------------------------------------------------------
    def finish(self) -> list[tuple[int, str, str]]:
        for line, local, code, reexport in self.imports:
            if reexport or local in self.dunder_all or local in self.used_names:
                continue
            if local == "annotations":  # from __future__ import annotations
                continue
            if self._noqa(line):
                continue
            # conftest/fixture side-effect imports are conventional
            if self.path.name == "conftest.py":
                continue
            self.problems.append((line, code, f"unused import: {local}"))
        return sorted(self.problems)


def run() -> int:
    bad = 0
    emitted: list[tuple[pathlib.Path, int, str]] = []
    for path in _iter_files():
        source = path.read_text()
        try:
            tree = ast.parse(source, filename=str(path))
        except SyntaxError as exc:  # E9 tier
            print(f"{path}:{exc.lineno}: E999 {exc.msg}")
            bad += 1
            continue
        checker = _Checker(path, tree, source)
        for line, code, msg in checker.finish():
            print(f"{path.relative_to(REPO)}:{line}: {code} {msg}")
            bad += 1
        if "dkg_tpu/" in path.as_posix():
            emitted.extend(
                (path, line, name) for line, name in checker.metric_names
            )
    bad += _check_metric_docs(emitted)
    return bad


def _check_metric_docs(emitted: list[tuple[pathlib.Path, int, str]]) -> int:
    """DKG011: every metric name library code emits must appear in the
    docs/observability.md metric reference (substring match — the docs
    render names in backticked table rows)."""
    docs = REPO / "docs" / "observability.md"
    try:
        reference = docs.read_text()
    except OSError:
        print(f"{docs.relative_to(REPO)}:1: DKG011 metric reference missing")
        return 1
    bad = 0
    seen: set[str] = set()
    for path, line, name in emitted:
        if name in _DKG011_UNDOCUMENTED_OK or name in reference:
            continue
        if name in seen:  # one report per name, not per emission site
            continue
        seen.add(name)
        print(
            f"{path.relative_to(REPO)}:{line}: DKG011 metric "
            f"{name!r} not documented in docs/observability.md's metric "
            "reference"
        )
        bad += 1
    return bad


if __name__ == "__main__":
    n = run()
    if n:
        print(f"\n{n} problem(s)", file=sys.stderr)
    sys.exit(1 if n else 0)
