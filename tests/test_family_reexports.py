"""Tier-1 wiring of scripts/check_family_reexports.py: the PR-1
re-export pattern (family modules re-exporting models/transformer.py's
serving protocol) has no compile-time guard — a serve symbol added to
transformer.py but missed in a family module only explodes
when an engine feature touches it at runtime. This test rots loudly
instead."""
import importlib.util
import os


def _load_checker():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
        "check_family_reexports.py",
    )
    spec = importlib.util.spec_from_file_location("check_family_reexports", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_all_families_reexport_full_serve_api():
    checker = _load_checker()
    missing = checker.check()
    assert not missing, (
        "family modules missing serve API symbols (add them to the "
        f"re-export block): {missing}"
    )


def test_guard_covers_the_engine_call_surface():
    """The guard's SERVE_API list must itself track what the serving
    stack actually calls — if any serve module grows a model hook that
    the list misses, the guard silently stops guarding. Originally this
    scanned ``self.model.<name>`` in engine.py alone; the quantized-KV
    work (PR 5) audited the whole package and widened the sweep so a
    hook called as ``engine.model.<name>`` from the scheduler,
    SpecInfer, beam or prefix-cache layers can't slip past either.
    (The quantized path itself added NO new hooks — it extends existing
    entry points with ``kv_quant=...`` kwargs, which re-exports carry
    by reference.)"""
    import glob
    import re

    checker = _load_checker()
    serve_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "flexflow_tpu", "serve",
    )
    called = {}
    for path in sorted(glob.glob(os.path.join(serve_dir, "*.py"))):
        src = open(path).read()
        # any attribute pulled off a ``model`` handle: self.model.X,
        # engine.model.X, self.engine.model.X, mod.model.X ...
        for name in re.findall(r"\bmodel\.(\w+)", src):
            called.setdefault(name, set()).add(os.path.basename(path))
    for name in ("__name__",):  # logging, not protocol
        called.pop(name, None)
    hooks = set(called) - set(checker.SERVE_API)
    assert not hooks, (
        "serve modules call model hooks the re-export guard misses: "
        f"{ {h: sorted(called[h]) for h in hooks} } — add them to "
        "scripts/check_family_reexports.py SERVE_API"
    )
