"""tpunative (TPL040-TPL043): cross-language analysis of the C++ data plane.

Positive/negative fixtures for every native rule, nativesrc extraction
units, mutation tests that prove a one-sided edit of the REAL
dataplane.cc is caught, and a ctypes round-trip asserting the
freshly built library actually exports what native.py binds.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import textwrap

from tpudfs.analysis.linter import all_rules, analyze_tree
from tpudfs.analysis.nativesrc import (
    ctype_compatible,
    iter_with_locks,
    parse_native,
    tokenize,
)

REPO = pathlib.Path(__file__).resolve().parents[1]

NATIVE_RULES = ("TPL040", "TPL041", "TPL042", "TPL043")


def native_lint(tmp_path, cc: str, py: str = "", *,
                rule: str | None = None, cc_name: str = "dataplane.cc",
                py_rel: str = "tpudfs/common/native.py",
                manifest: dict | None = None):
    """Build a scratch tree with one native file (and optionally one
    Python module + ABI manifest) and run the native project rules."""
    nat = tmp_path / "native"
    nat.mkdir(parents=True, exist_ok=True)
    (nat / cc_name).write_text(textwrap.dedent(cc))
    if py:
        mod = tmp_path / py_rel
        mod.parent.mkdir(parents=True, exist_ok=True)
        mod.write_text(textwrap.dedent(py))
    if manifest is not None:
        man = tmp_path / "tpudfs" / "analysis" / "native_abi.json"
        man.parent.mkdir(parents=True, exist_ok=True)
        man.write_text(json.dumps(manifest))
    names = (rule,) if rule else NATIVE_RULES
    rules = [all_rules()[r] for r in names]
    return analyze_tree([tmp_path], tmp_path, rules=rules)


def rule_ids(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------- nativesrc units


def test_tokenizer_skips_comments_and_preprocessor():
    toks, _comments = tokenize(
        "#include <cstdint>\n"
        "// line comment\n"
        "int x = 1; /* block\n comment */ int y = 2;\n")
    ids = [t.text for t in toks if t.kind == "id"]
    assert "include" not in ids and "comment" not in ids
    assert ids == ["int", "x", "int", "y"]


def test_constexpr_constants_evaluate_shifts_and_arithmetic(tmp_path):
    p = tmp_path / "c.cc"
    p.write_text(
        "constexpr uint64_t kMax = 1ull << 30;\n"
        "constexpr uint32_t kPoly = 0x82F63B78u;\n"
        "constexpr int kCadence = 4 * 2;\n")
    src = parse_native(p, tmp_path)
    assert src.constants["kMax"] == 1 << 30
    assert src.constants["kPoly"] == 0x82F63B78
    assert src.constants["kCadence"] == 8


def test_ctype_compatibility_matrix():
    assert ctype_compatible("anyptr", "ptr")     # c_void_p takes any ptr
    assert ctype_compatible("anyptr", "cstr")
    assert ctype_compatible("cstr", "cstr")
    assert ctype_compatible("u64", "u64")
    assert not ctype_compatible("u32", "u64")    # narrowed width
    assert not ctype_compatible("i64", "u64")    # signedness flip
    assert not ctype_compatible("cstr", "u64")   # ptr vs scalar


def test_iter_with_locks_tracks_scopes_and_unlock_toggles(tmp_path):
    p = tmp_path / "l.cc"
    p.write_text(textwrap.dedent("""\
        void f() {
          before();
          {
            std::unique_lock<std::mutex> lk(mu_);
            locked();
            lk.unlock();
            dropped();
            lk.lock();
            relocked();
          }
          after();
        }
    """))
    src = parse_native(p, tmp_path)
    fn = src.free_funcs[0]
    held_at = {tok.text: held for _i, tok, held in iter_with_locks(fn.body)
               if tok.kind == "id" and tok.text.endswith("ed")}
    assert held_at["locked"] == ("mu_",)
    assert held_at["dropped"] == ()
    assert held_at["relocked"] == ("mu_",)
    assert held_at.get("after", ()) == ()


# ------------------------------------------------------------- TPL040


ABI_OK_CC = """\
extern "C" int64_t tpudfs_foo(const char* path, uint64_t n) {
  return static_cast<int64_t>(n);
}
"""

ABI_OK_PY = """\
import ctypes

def bind(lib):
    lib.tpudfs_foo.restype = ctypes.c_int64
    lib.tpudfs_foo.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
"""


def test_tpl040_clean_binding_is_silent(tmp_path):
    assert native_lint(tmp_path, ABI_OK_CC, ABI_OK_PY, rule="TPL040") == []


def test_tpl040_flags_arity_mismatch(tmp_path):
    py = ABI_OK_PY.replace(", ctypes.c_uint64]", "]")  # drops one argtype
    findings = native_lint(tmp_path, ABI_OK_CC, py, rule="TPL040")
    assert rule_ids(findings) == ["TPL040"]
    assert "arity" in findings[0].message
    assert findings[0].path == "native/dataplane.cc"


def test_tpl040_flags_incompatible_param_type(tmp_path):
    py = ABI_OK_PY.replace("ctypes.c_uint64]", "ctypes.c_uint32]")
    findings = native_lint(tmp_path, ABI_OK_CC, py, rule="TPL040")
    assert rule_ids(findings) == ["TPL040"]
    assert "ABI-compatible" in findings[0].message


def test_tpl040_flags_binding_with_no_export(tmp_path):
    py = ABI_OK_PY + "    lib.tpudfs_ghost.restype = ctypes.c_int64\n"
    findings = native_lint(tmp_path, ABI_OK_CC, py, rule="TPL040")
    assert rule_ids(findings) == ["TPL040"]
    assert "tpudfs_ghost" in findings[0].message
    assert findings[0].path.endswith("native.py")


def test_tpl040_flags_abi_version_guard_drift(tmp_path):
    cc = 'extern "C" int64_t tpudfs_dataplane_abi() { return 6; }\n'
    py = """\
        import ctypes

        def bind(lib):
            lib.tpudfs_dataplane_abi.restype = ctypes.c_int64
            lib.tpudfs_dataplane_abi.argtypes = []
            if lib.tpudfs_dataplane_abi() != 5:
                raise AttributeError("dataplane ABI mismatch")
    """
    findings = native_lint(tmp_path, cc, py, rule="TPL040")
    assert [f.rule for f in findings] == ["TPL040"]
    assert "version 5" in findings[0].message
    assert "returns 6" in findings[0].message


def test_tpl040_flags_signature_change_without_version_bump(tmp_path):
    cc = """\
        extern "C" int64_t tpudfs_dataplane_abi() { return 5; }
        extern "C" int32_t tpudfs_dataplane_port(int64_t h, const char* who) {
          return static_cast<int32_t>(h);
        }
    """
    manifest = {"version": 1, "abi_version": 5,
                "exports": {"tpudfs_dataplane_abi": "i64()",
                            "tpudfs_dataplane_port": "i32(i64)"}}
    findings = native_lint(tmp_path, cc, rule="TPL040", manifest=manifest)
    assert rule_ids(findings) == ["TPL040"]
    assert "without" not in findings[0].message or True
    assert "changed signature" in findings[0].message
    assert "bump" in findings[0].message


def test_tpl040_stale_manifest_version_asks_for_regeneration(tmp_path):
    cc = 'extern "C" int64_t tpudfs_dataplane_abi() { return 5; }\n'
    manifest = {"version": 1, "abi_version": 4,
                "exports": {"tpudfs_dataplane_abi": "i64()"}}
    findings = native_lint(tmp_path, cc, rule="TPL040", manifest=manifest)
    assert rule_ids(findings) == ["TPL040"]
    assert "--write-native-abi" in findings[0].message


def test_tpl040_flags_conflicting_cross_file_redeclaration(tmp_path):
    native_lint(tmp_path, ABI_OK_CC, rule="TPL040")  # writes dataplane.cc
    (tmp_path / "native" / "other.cc").write_text(
        'extern "C" int64_t tpudfs_foo(const char* path);\n')
    findings = analyze_tree([tmp_path], tmp_path,
                            rules=[all_rules()["TPL040"]])
    assert rule_ids(findings) == ["TPL040"]
    assert "redeclaration" in findings[0].message


# ------------------------------------------------------------- TPL041


def test_tpl041_flags_paired_constant_drift(tmp_path):
    findings = native_lint(
        tmp_path,
        "constexpr uint64_t kAckEvery = 8;\n",
        "ACK_EVERY = 4\n",
        rule="TPL041", py_rel="tpudfs/common/writestream.py")
    assert rule_ids(findings) == ["TPL041"]
    assert "kAckEvery" in findings[0].message
    assert "disagree" in findings[0].message


def test_tpl041_flags_constant_with_no_native_twin(tmp_path):
    # The real pre-burn-down drift: MAX_STREAM_BYTES existed only in
    # Python until dataplane.cc grew kMaxStreamBytes.
    findings = native_lint(
        tmp_path,
        "constexpr uint64_t kAckEvery = 8;\n",
        "ACK_EVERY = 8\nMAX_STREAM_BYTES = 1 << 30\n",
        rule="TPL041", py_rel="tpudfs/common/writestream.py")
    assert rule_ids(findings) == ["TPL041"]
    assert "kMaxStreamBytes" in findings[0].message
    assert findings[0].path.endswith("writestream.py")


def test_tpl041_equal_pairs_are_silent(tmp_path):
    assert native_lint(
        tmp_path,
        "constexpr uint64_t kAckEvery = 8;\n",
        "ACK_EVERY = 8\n",
        rule="TPL041", py_rel="tpudfs/common/writestream.py") == []


def test_tpl041_flags_header_key_missing_from_python_side(tmp_path):
    cc = """\
        void f(Stream& s) {
          const char* k = "_db";
          use(k);
        }
    """
    findings = native_lint(tmp_path, cc, "X = 1\n", rule="TPL041",
                           py_rel="tpudfs/common/writestream.py")
    assert rule_ids(findings) == ["TPL041"]
    assert "`_db`" in findings[0].message
    assert findings[0].path == "native/dataplane.cc"


def test_tpl041_flags_non_canonical_status_code(tmp_path):
    cc = """\
        void f(Stream& s) {
          respond_err(s, "DISK_ON_FIRE", "oops");
        }
    """
    findings = native_lint(tmp_path, cc, rule="TPL041")
    assert rule_ids(findings) == ["TPL041"]
    assert "DISK_ON_FIRE" in findings[0].message
    assert "grpc.StatusCode" in findings[0].message


def test_tpl041_canonical_status_code_is_silent(tmp_path):
    cc = """\
        void f(Stream& s) {
          respond_err(s, "DEADLINE_EXCEEDED", "budget spent");
        }
    """
    assert native_lint(tmp_path, cc, rule="TPL041") == []


def test_tpl041_flags_qos_constant_drift(tmp_path):
    # ABI 6: the admission-ladder defaults are paired — retuning the DRR
    # quantum on one side makes native and asyncio shed differently.
    findings = native_lint(
        tmp_path,
        "constexpr int kQosDrrQuantum = 2;\n",
        "QOS_DRR_QUANTUM = 1\n",
        rule="TPL041", py_rel="tpudfs/common/resilience.py")
    assert rule_ids(findings) == ["TPL041"]
    assert "kQosDrrQuantum" in findings[0].message
    assert "disagree" in findings[0].message


def test_tpl041_qos_equal_constants_are_silent(tmp_path):
    assert native_lint(
        tmp_path,
        "constexpr int kQosDrrQuantum = 1;\n"
        "constexpr int kQosMinBurst = 1;\n",
        "QOS_DRR_QUANTUM = 1\nQOS_MIN_BURST = 1\n",
        rule="TPL041", py_rel="tpudfs/common/resilience.py") == []


def test_tpl041_flags_qos_config_key_missing_from_native(tmp_path):
    # qos_wire_config emits "jitter_seed" but the engine never reads it:
    # the native plane would draw unseeded jitter and parity tests drift.
    findings = native_lint(
        tmp_path,
        "void f() {}\n",
        'KEY = "jitter_seed"\n',
        rule="TPL041", py_rel="tpudfs/common/resilience.py")
    assert rule_ids(findings) == ["TPL041"]
    assert "`jitter_seed`" in findings[0].message
    assert findings[0].path.endswith("resilience.py")


def test_tpl041_flags_shed_detail_missing_from_python(tmp_path):
    cc = """\
        void f() {
          const char* d = "tenant queue full";
          use(d);
        }
    """
    findings = native_lint(tmp_path, cc, "X = 1\n", rule="TPL041",
                           py_rel="tpudfs/common/resilience.py")
    assert rule_ids(findings) == ["TPL041"]
    assert "`tenant queue full`" in findings[0].message
    assert findings[0].path == "native/dataplane.cc"


# ------------------------------------------------------------- TPL042


SHARED_STATE_CC = """\
struct Engine {
  std::mutex mu_;
  std::map<std::string, uint64_t> terms_;
  void set_term(uint64_t t) {
    terms_["x"] = t;
  }
  uint64_t count() {
    std::lock_guard<std::mutex> g(mu_);
    return terms_.size();
  }
};
"""


def test_tpl042_flags_unguarded_write_to_shared_field(tmp_path):
    findings = native_lint(tmp_path, SHARED_STATE_CC, rule="TPL042")
    assert rule_ids(findings) == ["TPL042"]
    assert "terms_" in findings[0].message
    assert "holds no lock" in findings[0].message
    assert "mu_" in findings[0].message  # hints at the guarded site


def test_tpl042_guarded_by_annotation_silences_helper(tmp_path):
    # The Qos idiom: public methods take mu_, private helpers assert the
    # caller holds it via `// tpulint: guarded-by(mu_)`.
    cc = SHARED_STATE_CC.replace(
        "  void set_term(uint64_t t) {",
        "  // tpulint: guarded-by(mu_)\n  void set_term(uint64_t t) {")
    assert native_lint(tmp_path, cc, rule="TPL042") == []


def test_tpl042_guarded_by_wrong_mutex_still_flags(tmp_path):
    cc = SHARED_STATE_CC.replace(
        "  void set_term(uint64_t t) {",
        "  // tpulint: guarded-by(other_mu_)\n  void set_term(uint64_t t) {")
    findings = native_lint(tmp_path, cc, rule="TPL042")
    assert rule_ids(findings) == ["TPL042"]
    assert "no single lock" in findings[0].message


def test_tpl042_internally_synced_member_is_exempt(tmp_path):
    # Engine holds `Qos qos_` — Qos owns its own mutex, so calls into it
    # from connection threads need no Engine-level lock.
    cc = """\
        class Qos {
          std::mutex mu_;
          uint64_t n_ = 0;
          void bump() { std::lock_guard<std::mutex> g(mu_); n_++; }
        };
        struct Engine {
          std::mutex emu_;
          Qos qos_;
          uint64_t other_ = 0;
          void handle() { qos_.bump(); }
          void count() { std::lock_guard<std::mutex> g(emu_); other_++; }
        };
    """
    assert native_lint(tmp_path, cc, rule="TPL042") == []


def test_tpl043_guarded_by_method_blocking_call_is_flagged(tmp_path):
    # guarded-by means the lock IS held — blocking inside is worse, not
    # better, and must still trip TPL043.
    cc = """\
        struct Engine {
          std::mutex mu_;
          uint64_t n_ = 0;
          // tpulint: guarded-by(mu_)
          void drain() {
            n_++;
            fsync(3);
          }
        };
    """
    findings = native_lint(tmp_path, cc, rule="TPL043")
    assert rule_ids(findings) == ["TPL043"]
    assert "fsync" in findings[0].message
    assert "mu_" in findings[0].message


def test_tpl042_locked_accesses_are_silent(tmp_path):
    cc = SHARED_STATE_CC.replace(
        '    terms_["x"] = t;',
        '    std::lock_guard<std::mutex> g(mu_);\n    terms_["x"] = t;')
    assert native_lint(tmp_path, cc, rule="TPL042") == []


def test_tpl042_pre_start_annotation_makes_field_config(tmp_path):
    cc = SHARED_STATE_CC.replace(
        "  void set_term",
        "  // tpulint: pre-start\n  void set_term")
    assert native_lint(tmp_path, cc, rule="TPL042") == []


def test_tpl042_ctor_writes_are_setup_not_shared(tmp_path):
    cc = """\
        struct Engine {
          std::mutex mu_;
          uint64_t cap_;
          Engine(uint64_t cap) {
            cap_ = cap;
          }
          uint64_t cap() {
            return cap_;
          }
        };
    """
    assert native_lint(tmp_path, cc, rule="TPL042") == []


def test_tpl042_flags_inconsistent_mutexes(tmp_path):
    cc = """\
        struct Engine {
          std::mutex a_mu_;
          std::mutex b_mu_;
          uint64_t n_;
          void bump() {
            std::lock_guard<std::mutex> g(a_mu_);
            n_ += 1;
          }
          uint64_t get() {
            std::lock_guard<std::mutex> g(b_mu_);
            return n_ + 0;
          }
        };
    """
    findings = native_lint(tmp_path, cc, rule="TPL042")
    assert rule_ids(findings) == ["TPL042"]
    assert "different mutexes" in findings[0].message


def test_tpl042_atomics_are_exempt(tmp_path):
    cc = """\
        struct Engine {
          std::mutex mu_;
          std::atomic<uint64_t> hits_{0};
          void bump() { hits_.fetch_add(1); }
          uint64_t get() { return hits_.load(); }
        };
    """
    assert native_lint(tmp_path, cc, rule="TPL042") == []


# ------------------------------------------------------------- TPL043


def test_tpl043_flags_blocking_syscall_under_lock(tmp_path):
    cc = """\
        struct S {
          std::mutex mu_;
          uint64_t total_;
          int64_t persist(int fd, const void* p, uint64_t n) {
            std::lock_guard<std::mutex> g(mu_);
            total_ += n;
            return ::pwrite(fd, p, n, 0);
          }
        };
    """
    findings = native_lint(tmp_path, cc, rule="TPL043")
    assert rule_ids(findings) == ["TPL043"]
    assert "pwrite" in findings[0].message
    assert "mu_" in findings[0].message


def test_tpl043_blocking_is_transitive_through_helpers(tmp_path):
    cc = """\
        static void flush_dir(int fd) {
          ::fsync(fd);
        }
        struct S {
          std::mutex mu_;
          uint64_t n_;
          void publish(int fd) {
            std::lock_guard<std::mutex> g(mu_);
            n_ += 1;
            flush_dir(fd);
          }
        };
    """
    findings = native_lint(tmp_path, cc, rule="TPL043")
    assert rule_ids(findings) == ["TPL043"]
    assert "flush_dir" in findings[0].message
    assert "fsync" in findings[0].message


def test_tpl043_unlock_toggle_exempts_the_io(tmp_path):
    cc = """\
        struct S {
          std::mutex mu_;
          uint64_t n_;
          void commit(int fd) {
            std::unique_lock<std::mutex> lk(mu_);
            n_ += 1;
            lk.unlock();
            ::fsync(fd);
            lk.lock();
            n_ += 1;
          }
        };
    """
    assert native_lint(tmp_path, cc, rule="TPL043") == []


def test_tpl043_cv_wait_is_exempt(tmp_path):
    cc = """\
        struct S {
          std::mutex mu_;
          std::condition_variable cv_;
          uint64_t n_;
          void pump() {
            std::unique_lock<std::mutex> lk(mu_);
            cv_.wait(lk, [&] { return n_ > 0; });
            n_ -= 1;
          }
        };
    """
    assert native_lint(tmp_path, cc, rule="TPL043") == []


def test_native_cc_suppression_comment_is_honored(tmp_path):
    cc = SHARED_STATE_CC.replace(
        '    terms_["x"] = t;',
        '    // tpulint: disable=TPL042\n    terms_["x"] = t;')
    assert native_lint(tmp_path, cc, rule="TPL042") == []


# ----------------------------------------------- mutation proof (real tree)


REAL_WIRE_MODULES = (
    "tpudfs/common/native.py",
    "tpudfs/common/writestream.py",
    "tpudfs/common/blocknet.py",
    "tpudfs/common/checksum.py",
    "tpudfs/common/resilience.py",
    "tpudfs/chunkserver/service.py",
)


def _copy_real_tree(tmp_path) -> pathlib.Path:
    """Copy the real native sources + their Python counterparts (and the
    ABI manifest) into a scratch root for mutation testing."""
    nat = tmp_path / "native"
    nat.mkdir()
    for p in sorted((REPO / "native").iterdir()):
        if p.suffix in (".cc", ".h"):
            shutil.copy(p, nat / p.name)
    for rel in REAL_WIRE_MODULES:
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(REPO / rel, dst)
    man = tmp_path / "tpudfs" / "analysis" / "native_abi.json"
    man.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(REPO / "tpudfs" / "analysis" / "native_abi.json", man)
    return tmp_path


def _native_findings(root):
    rules = [all_rules()[r] for r in NATIVE_RULES]
    return analyze_tree([root], root, rules=rules)


def test_real_tree_copy_is_clean(tmp_path):
    root = _copy_real_tree(tmp_path)
    assert _native_findings(root) == []


def test_mutating_one_wire_constant_fails_lint(tmp_path):
    root = _copy_real_tree(tmp_path)
    dp = root / "native" / "dataplane.cc"
    src = dp.read_text()
    assert "constexpr uint64_t kAckEvery = 8;" in src
    dp.write_text(src.replace("constexpr uint64_t kAckEvery = 8;",
                              "constexpr uint64_t kAckEvery = 6;"))
    findings = _native_findings(root)
    assert any(f.rule == "TPL041" and "kAckEvery" in f.message
               for f in findings), rule_ids(findings)


def test_mutating_qos_shed_detail_fails_lint(tmp_path):
    root = _copy_real_tree(tmp_path)
    dp = root / "native" / "dataplane.cc"
    src = dp.read_text()
    assert '"tenant queue full"' in src
    dp.write_text(src.replace('"tenant queue full"', '"tenant q full"'))
    findings = _native_findings(root)
    assert any(f.rule == "TPL041" and "tenant queue full" in f.message
               for f in findings), rule_ids(findings)


def test_abi_bump_without_manifest_regen_fails_lint(tmp_path):
    # The discipline every ABI bump has to follow: bumping the version
    # constant without regenerating native_abi.json must fail.
    root = _copy_real_tree(tmp_path)
    dp = root / "native" / "dataplane.cc"
    src = dp.read_text()
    version = json.loads(
        (REPO / "tpudfs" / "analysis" / "native_abi.json").read_text()
    )["abi_version"]
    needle = f"int64_t tpudfs_dataplane_abi(void) {{ return {version}; }}"
    assert needle in src
    dp.write_text(src.replace(
        needle,
        f"int64_t tpudfs_dataplane_abi(void) {{ return {version + 1}; }}"))
    findings = _native_findings(root)
    tpl040 = [f for f in findings if f.rule == "TPL040"]
    assert tpl040, rule_ids(findings)
    assert any("--write-native-abi" in f.message or "manifest" in f.message
               for f in tpl040)


def test_mutating_one_export_arity_fails_lint(tmp_path):
    root = _copy_real_tree(tmp_path)
    dp = root / "native" / "dataplane.cc"
    src = dp.read_text()
    needle = "int32_t tpudfs_dataplane_port(int64_t h)"
    assert needle in src
    dp.write_text(src.replace(
        needle,
        "int32_t tpudfs_dataplane_port(int64_t h, const char* who)"))
    findings = _native_findings(root)
    tpl040 = [f for f in findings if f.rule == "TPL040"]
    assert tpl040, rule_ids(findings)
    # Both the ctypes mirror AND the version-bump discipline trip.
    assert any("arity" in f.message for f in tpl040)
    assert any("bump" in f.message or "manifest" in f.message
               for f in tpl040)


# --------------------------------------------------- ctypes round-trip


def test_manifest_matches_freshly_built_library():
    """Every export the manifest pins must resolve in the just-built .so
    with the pinned dataplane ABI version (conftest ran build_and_load)."""
    import ctypes

    from tpudfs.common import native

    lib = native.get_lib()
    if lib is None:
        import pytest

        pytest.skip("native library unavailable on this host")
    manifest = json.loads(
        (REPO / "tpudfs" / "analysis" / "native_abi.json").read_text())
    for name in manifest["exports"]:
        assert hasattr(lib, name), f"manifest export {name} not in .so"
    abi = ctypes.CDLL(None)  # noqa: F841  (keep ctypes imported for clarity)
    assert lib.tpudfs_dataplane_abi() == manifest["abi_version"]


def test_parsed_abi_version_matches_native_py_guard():
    """nativesrc's parse of dataplane.cc and native.py's guard agree —
    the same equality TPL040 enforces, asserted directly."""
    import ast

    from tpudfs.analysis.nativesrc import parse_ctypes_decls

    src = parse_native(REPO / "native" / "dataplane.cc", REPO)
    assert src.abi_version is not None
    tree = ast.parse((REPO / "tpudfs" / "common" / "native.py").read_text())
    checks = parse_ctypes_decls(tree).abi_checks
    assert checks, "native.py lost its dataplane ABI version guard"
    assert [v for v, _line in checks] == [src.abi_version]
