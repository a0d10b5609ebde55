"""Storage registry tests (reference `Storage.scala:40-296` env-var wiring)."""

import pytest

from predictionio_tpu.storage import (
    MemoryEventStore,
    SQLiteEventStore,
    Storage,
    StorageError,
)


def test_default_sqlite_under_home(tmp_path):
    s = Storage(env={"PIO_TPU_HOME": str(tmp_path)})
    es = s.get_event_store()
    assert isinstance(es, SQLiteEventStore)
    s.verify_all_data_objects()
    assert (tmp_path / "eventdata.db").exists()
    assert (tmp_path / "metadata.db").exists()
    assert (tmp_path / "models").is_dir()
    s.close()


def test_env_var_source_mapping(tmp_path):
    s = Storage(env={
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_TPU_HOME": str(tmp_path),
    })
    assert isinstance(s.get_event_store(), MemoryEventStore)
    s.close()


def test_env_var_sqlite_path(tmp_path):
    s = Storage(env={
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
        "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "ev.db"),
    })
    es = s.get_event_store()
    es.init_channel(1)
    assert (tmp_path / "ev.db").exists()
    s.close()


def test_missing_source_type_errors():
    s = Storage(env={"PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "NOPE"})
    with pytest.raises(StorageError):
        s.get_event_store()


def test_storage_fixture(storage_memory):
    storage_memory.verify_all_data_objects()


def test_all_shell_scripts_parse():
    """Every shipped shell script must at least pass `bash -n`."""
    import subprocess
    from pathlib import Path

    root = Path(__file__).parent.parent
    candidates = (
        list((root / "bin").iterdir())
        + list((root / "tools").iterdir())
        + list((root / "conf").glob("*.sh*"))
    )
    scripts = sorted(
        p for p in candidates
        if p.is_file()
        and p.read_bytes()[:32].startswith(b"#!")
        and b"bash" in p.read_bytes()[:32]
    )
    # the gate scripts MUST be covered: a syntax error there would
    # skip/fail every commit, not just one battery step
    names = {p.name for p in scripts}
    assert {"pre-commit", "gate.sh"} <= names
    for sc in scripts:
        proc = subprocess.run(
            ["bash", "-n", str(sc)], capture_output=True, text=True
        )
        assert proc.returncode == 0, f"{sc.name}: {proc.stderr}"


def test_shipped_env_template_parses_and_boots(tmp_path):
    """`conf/pio-env-tpu.template` is the ops on-ramp (reference
    `conf/pio-env.sh.template:36-60`): every exported variable must be
    one the registry actually honors, and the configuration it
    describes must boot all three repositories."""
    import re
    from pathlib import Path

    template = (
        Path(__file__).parent.parent / "conf" / "pio-env-tpu.template"
    ).read_text()
    env = {}
    for line in template.splitlines():
        line = line.strip()
        if line.startswith("# export "):
            line = line[2:]  # commented-out optional knobs parse too
        if not line.startswith("export "):
            continue
        key, _, val = line[len("export "):].partition("=")
        env[key] = val
    # substitute shell vars against a scratch home
    env["PIO_TPU_HOME"] = str(tmp_path / "pio")
    env["HOME"] = str(tmp_path)
    for k, v in env.items():
        env[k] = re.sub(
            r"\$(\w+)", lambda m: env.get(m.group(1), m.group(0)), v
        )
    # every PIO_* key in the template is one the code reads
    known = {
        "PIO_TPU_HOME", "PIO_TPU_SCAN_CACHE",
        "PIO_TPU_VMEM_BYTES", "PIO_TPU_BENCH_BUDGET_S",
    }
    for key in env:
        if key.startswith("PIO_TPU_"):
            assert key in known, f"template documents unknown knob {key}"
        elif key.startswith("PIO_"):
            assert re.fullmatch(
                r"PIO_STORAGE_(REPOSITORIES_(METADATA|EVENTDATA|MODELDATA)"
                r"_(NAME|SOURCE)|SOURCES_\w+_(TYPE|PATH))", key
            ), f"template documents unknown storage key {key}"
    s = Storage(env={k: v for k, v in env.items() if k.startswith("PIO_")})
    s.verify_all_data_objects()
    # the template's explicit sources landed where it says they do
    assert (tmp_path / "pio" / "eventdata.db").exists()
    assert (tmp_path / "pio" / "models").is_dir()
    s.close()


def test_pluggable_backend_via_dotted_type(tmp_path):
    """A third-party EventStore registers via env config ONLY — a
    dotted import path in the TYPE var, no framework edit (the
    `Storage.scala:183-224` reflective extension point; VERDICT r4 #6).
    The backend receives the source's full config dict and serves the
    startup self-check end to end."""
    from fixtures import ToyEventStore

    s = Storage(env={
        "PIO_TPU_HOME": str(tmp_path),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "TOY",
        "PIO_STORAGE_SOURCES_TOY_TYPE": "fixtures.ToyEventStore",
        "PIO_STORAGE_SOURCES_TOY_FLAVOR": "banana",
    })
    es = s.get_event_store()
    assert isinstance(es, ToyEventStore)
    # full source config arrives, custom keys included
    assert es.conf["flavor"] == "banana"
    assert es.conf["type"] == "fixtures.ToyEventStore"
    # and it actually serves storage traffic (metadata stays builtin)
    s.verify_all_data_objects()
    s.close()


def test_pluggable_backend_errors_are_loud():
    # unimportable module
    s = Storage(env={
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "X",
        "PIO_STORAGE_SOURCES_X_TYPE": "no.such.module.Cls",
    })
    with pytest.raises(StorageError, match="cannot load"):
        s.get_event_store()
    # importable module, missing attribute
    s = Storage(env={
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "X",
        "PIO_STORAGE_SOURCES_X_TYPE": "fixtures.NoSuchStore",
    })
    with pytest.raises(StorageError, match="cannot load"):
        s.get_event_store()
    # constructor failure surfaces the config keys
    s = Storage(env={
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "X",
        "PIO_STORAGE_SOURCES_X_TYPE": "fixtures.ExplodingStore",
    })
    with pytest.raises(StorageError, match="failed to initialize"):
        s.get_event_store()
    # dotless unknown names still get the old loud error
    s = Storage(env={
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "X",
        "PIO_STORAGE_SOURCES_X_TYPE": "hbase",
    })
    with pytest.raises(StorageError, match="unknown event store"):
        s.get_event_store()
