"""Importing dkg_tpu must never initialise a jax backend.

Platform forcing (parallel/hostmesh.py) only works before the first
backend initialisation.  A module-level device constant anywhere in the
package (e.g. ``jnp.uint32(...)`` at import scope) would initialise the
backend during ``import dkg_tpu`` itself — on a machine with a chip
that means claiming the real TPU before the CPU mesh can be forced.  Run in a subprocess so this process's already-live
backend doesn't mask the check.
"""

import subprocess
import sys


def test_package_import_initialises_no_backend():
    code = (
        "import dkg_tpu, dkg_tpu.fields, dkg_tpu.groups, dkg_tpu.crypto, "
        "dkg_tpu.dkg, dkg_tpu.poly, dkg_tpu.ops, dkg_tpu.parallel, "
        "dkg_tpu.net, dkg_tpu.utils, dkg_tpu.service\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, f'backends initialised at import: {list(xb._backends)}'\n"
        "print('clean')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120
    )
    assert out.returncode == 0, out.stderr
    assert "clean" in out.stdout


def test_lint_lite_clean():
    """The AST lint gate (scripts/lint_lite.py) stays clean.

    CI's blocking ruff/mypy jobs are the authoritative gate (reference
    parity: clippy --deny warnings); this keeps the committed baseline
    lint-clean from inside the default test tier, since the dev image
    has no linter installed.
    """
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts"))
    try:
        import lint_lite
    finally:
        sys.path.pop(0)
    assert lint_lite.run() == 0, "lint_lite found problems (see stdout)"


def test_lint_dkg005_bans_raw_writes_in_net():
    """DKG005: net-layer code persists state only through the WAL —
    write-mode open(), Path.write_bytes/.write_text, and fd-level
    os.open are flagged everywhere in dkg_tpu/net/ except the WAL
    implementation itself."""
    import ast
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts"))
    try:
        import lint_lite
    finally:
        sys.path.pop(0)

    src = (
        "import os\n"
        "def f(p):\n"
        "    open(p, 'wb').write(b'x')\n"
        "    open(p, mode='a').write('x')\n"
        "    p.write_bytes(b'x')\n"
        "    p.write_text('x')\n"
        "    os.open(p, os.O_WRONLY)\n"
        "    open(p).read()\n"  # read-mode: fine
    )
    tree = ast.parse(src)
    codes = [
        c
        for _, c, _ in lint_lite._Checker(
            pathlib.Path("dkg_tpu/net/evil.py"), tree, src
        ).finish()
    ]
    assert codes.count("DKG005") == 5, codes
    # the WAL implementation is the sanctioned fd-level writer
    codes = [
        c
        for _, c, _ in lint_lite._Checker(
            pathlib.Path("dkg_tpu/net/checkpoint.py"), tree, src
        ).finish()
    ]
    assert "DKG005" not in codes, codes
    # and the rule is net-scoped: the same source elsewhere is clean
    codes = [
        c
        for _, c, _ in lint_lite._Checker(
            pathlib.Path("dkg_tpu/dkg/elsewhere.py"), tree, src
        ).finish()
    ]
    assert "DKG005" not in codes, codes


def test_lint_dkg004_allows_the_measured_blake2b_loop_in_fiat_shamir_rho():
    """DKG004's second half: a ``hashlib.blake2b`` in a loop (or a
    comprehension) of a dkg/ hot module is flagged, but for the legs where
    the loop was timed against ``blake2b_batch`` and won, or is the oracle:
    ``fiat_shamir_rho`` (PR 37) and ``_dealer_row_digests``."""
    import ast
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts"))
    try:
        import lint_lite
    finally:
        sys.path.pop(0)

    def codes(src, path):
        checker = lint_lite._Checker(pathlib.Path(path), ast.parse(src), src)
        return [c for _, c, _ in checker.finish()]

    body = (
        "    blake2b = hashlib.blake2b\n"
        "    return b''.join(blake2b(t + bytes([j])).digest() for j in range(n))\n"
    )
    src = "import hashlib\ndef {name}(t, n):\n" + body
    hot = "dkg_tpu/dkg/ceremony.py"
    assert "DKG004" not in codes(src.format(name="fiat_shamir_rho"), hot)
    assert "DKG004" not in codes(src.format(name="_dealer_row_digests"), hot)
    assert codes(src.format(name="derive_rho"), hot).count("DKG004") == 1
    # one hash outside any loop is no loop, and the rule is scoped to the
    # batch hot modules of dkg_tpu/dkg/
    once = "import hashlib\ndef derive_rho(t):\n    return hashlib.blake2b(t).digest()\n"
    assert "DKG004" not in codes(once, hot)
    assert "DKG004" not in codes(src.format(name="derive_rho"), "dkg_tpu/net/elsewhere.py")


def test_lint_dkg012_bans_raw_socket_io_in_net():
    """DKG012: every socket send/receive in dkg_tpu/net/ flows through
    the counted wire helpers so net_wire_bytes_total stays exact —
    raw .sendall/.send/.recv/.recv_into elsewhere is flagged; the
    helpers themselves and checkpoint.py (file IO) are exempt."""
    import ast
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts"))
    try:
        import lint_lite
    finally:
        sys.path.pop(0)

    src = (
        "def leak(sock, buf):\n"
        "    sock.sendall(b'x')\n"
        "    sock.send(b'x')\n"
        "    sock.recv(4)\n"
        "    sock.recv_into(buf)\n"
        "def _wire_send(sock, data):\n"
        "    sock.sendall(data)\n"  # the counted helper itself: sanctioned
    )
    tree = ast.parse(src)
    codes = [
        c
        for _, c, _ in lint_lite._Checker(
            pathlib.Path("dkg_tpu/net/evil.py"), tree, src
        ).finish()
    ]
    assert codes.count("DKG012") == 4, codes
    # net-scoped: the same source outside dkg_tpu/net/ is clean
    codes = [
        c
        for _, c, _ in lint_lite._Checker(
            pathlib.Path("dkg_tpu/utils/elsewhere.py"), tree, src
        ).finish()
    ]
    assert "DKG012" not in codes, codes
    # checkpoint.py is out of scope (WAL, fd-level file IO)
    codes = [
        c
        for _, c, _ in lint_lite._Checker(
            pathlib.Path("dkg_tpu/net/checkpoint.py"), tree, src
        ).finish()
    ]
    assert "DKG012" not in codes, codes


def test_lint_dkg007_bans_raw_config_and_spawns_in_service():
    """DKG007: service code reads knobs only through utils.envknobs
    (no raw ``os.environ`` / ``os.getenv``) and spawns execution
    contexts only in scheduler.py (the worker pool's single owner).
    The rule is scoped to dkg_tpu/service/ — the same source elsewhere
    is clean."""
    import ast
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts"))
    try:
        import lint_lite
    finally:
        sys.path.pop(0)

    src = (
        "import os, threading\n"
        "from concurrent.futures import ThreadPoolExecutor\n"
        "def f():\n"
        "    a = os.environ['DKG_TPU_SERVICE_CONCURRENCY']\n"
        "    b = os.getenv('DKG_TPU_SERVICE_QUEUE_DEPTH')\n"
        "    threading.Thread(target=f).start()\n"
        "    ThreadPoolExecutor(2)\n"
        "    return a, b\n"
    )
    tree = ast.parse(src)

    def codes_for(path: str) -> list:
        return [
            c
            for _, c, _ in lint_lite._Checker(
                pathlib.Path(path), tree, src
            ).finish()
            if c == "DKG007"
        ]

    # environ + getenv + Thread + ThreadPoolExecutor = 4 findings
    assert len(codes_for("dkg_tpu/service/engine.py")) == 4
    # scheduler.py owns the worker pool: spawns allowed, raw config not
    assert len(codes_for("dkg_tpu/service/scheduler.py")) == 2
    # the rule is service-scoped
    assert codes_for("dkg_tpu/net/elsewhere.py") == []
    assert codes_for("scripts/tool.py") == []


def test_lint_dkg010_bans_silent_swallows_and_bare_runtimeerror():
    """DKG010: serving-path code (dkg_tpu/service/ and dkg_tpu/sign/)
    may catch Exception only if the handler re-raises or records the
    failure, and must raise the typed taxonomy instead of a bare
    RuntimeError.  The rule is scoped — the same source elsewhere is
    clean."""
    import ast
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts"))
    try:
        import lint_lite
    finally:
        sys.path.pop(0)

    src = (
        "def swallow():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception:\n"
        "        result = None\n"
        "def recorded(metrics):\n"
        "    try:\n"
        "        work()\n"
        "    except Exception as exc:\n"
        "        metrics.inc('service_failed_total')\n"
        "def reraised():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception:\n"
        "        raise\n"
        "def contained(self, convoy, exc, t0):\n"
        "    try:\n"
        "        work()\n"
        "    except Exception as exc:\n"
        "        self._isolate(convoy, exc, t0)\n"
        "def typed_only():\n"
        "    raise RuntimeError('use errors.PoisonedRequest instead')\n"
        "def narrow():\n"
        "    try:\n"
        "        work()\n"
        "    except ValueError:\n"  # narrow catches are out of scope
        "        pass\n"
    )
    tree = ast.parse(src)

    def codes_for(path: str) -> list:
        return [
            c
            for _, c, _ in lint_lite._Checker(
                pathlib.Path(path), tree, src
            ).finish()
            if c == "DKG010"
        ]

    # one silent swallow + one bare RuntimeError = 2 findings, in both
    # serving-path packages
    assert len(codes_for("dkg_tpu/service/evil.py")) == 2
    assert len(codes_for("dkg_tpu/sign/evil.py")) == 2
    # the rule is serving-path-scoped
    assert codes_for("dkg_tpu/dkg/elsewhere.py") == []
    assert codes_for("tests/test_x.py") == []


def test_lint_dkg017_bans_placement_drops_outside_helpers():
    """DKG017: fleet.py may not remove ``_placed`` entries outside the
    sanctioned eviction/manifest helpers — a del/pop/clear anywhere
    else is a silent placement drop the failover machinery exists to
    prevent."""
    import ast
    import pathlib

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts"))
    try:
        import lint_lite
    finally:
        sys.path.pop(0)

    src = (
        "class F:\n"
        "    def rogue(self, cid):\n"
        "        del self._placed[cid]\n"
        "        self._placed.pop(cid, None)\n"
        "        self._placed.clear()\n"
        "        self._placed[cid] = [None, False]\n"  # adding: fine
        "        x = self._placed.get(cid)\n"  # reading: fine
        "    def _evict_placed(self, ws):\n"
        "        del self._placed['a']\n"  # sanctioned helper
        "    def _adopt_manifest(self, st, w, m):\n"
        "        self._placed.pop('a', None)\n"  # sanctioned helper
        "    def close(self):\n"
        "        self._placed.clear()\n"  # sanctioned helper
    )
    tree = ast.parse(src)

    def codes_for(path):
        return [
            c
            for _, c, _ in lint_lite._Checker(
                pathlib.Path(path), tree, src
            ).finish()
            if c == "DKG017"
        ]

    assert len(codes_for("dkg_tpu/service/fleet.py")) == 3
    # the rule is fleet-scoped: the same source elsewhere is clean
    assert codes_for("dkg_tpu/service/scheduler.py") == []
    assert codes_for("dkg_tpu/dkg/elsewhere.py") == []


def test_hostmesh_import_is_lightweight():
    # An image may preload jax itself, so "jax not in sys.modules" is
    # not the invariant; assert the real ones: no
    # backend initialised, and none of the heavy compute modules pulled.
    code = (
        "import sys\n"
        "from dkg_tpu.parallel.hostmesh import force_cpu_mesh\n"
        "heavy = [m for m in sys.modules if m.startswith('dkg_tpu.') and\n"
        "         m.split('.')[1] in ('fields', 'groups', 'crypto', 'dkg', 'ops', 'poly')]\n"
        "assert not heavy, f'hostmesh import dragged in {heavy}'\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, 'hostmesh import initialised a backend'\n"
        "print('clean')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120
    )
    assert out.returncode == 0, out.stderr
    assert "clean" in out.stdout
