"""Cluster sysperf probe tests (reference service/autotune_system.py:16+,
validated with a local `bash -c` shim in place of ssh)."""

import json

from bagua_tpu.service.autotune_system import parse_args, probe_host, sysperf


def test_probe_host_parses_json_lines(tmp_path):
    fake = tmp_path / "probe.py"
    fake.write_text(
        'print("noise")\n'
        'import json\n'
        'print(json.dumps({"metrics": {"tokens_per_s_per_chip": '
        '{"value": 12.5}}}))\n'
    )
    args = parse_args([
        "--host_list", "hostA", "--ssh_cmd", "bash -c",
        "--python", "python",
    ])
    # redirect the probe command at the fake script
    from bagua_tpu.service import autotune_system

    autotune_system.PROBE = str(fake)
    r = probe_host(args, "hostA")
    assert r["ok"] and autotune_system._score(r) == 12.5


def test_sysperf_flags_straggler(tmp_path, capfd):
    from bagua_tpu.service import autotune_system

    args = parse_args([
        "--host_list", "h1,h2,h3",
        # each "host" runs the same probe; make h3 slow via hostname switch
        "--ssh_cmd", "bash -c",
        "--python", "python",
    ])
    probe = tmp_path / "probe.py"
    probe.write_text(
        "import json, os\n"
        "print(json.dumps({'metrics': {'tokens_per_s_per_chip': "
        "{'value': 100.0}}}))\n"
    )
    autotune_system.PROBE = str(probe)
    rc = sysperf(args)
    out, _ = capfd.readouterr()
    lines = [json.loads(l) for l in out.splitlines() if l.strip()]
    assert rc == 0
    assert all(not l["straggler"] for l in lines)

    # now a failing host
    args2 = parse_args([
        "--host_list", "h1,h2",
        "--ssh_cmd", "bash -c",
        "--python", "false &&",
    ])
    rc2 = sysperf(args2)
    assert rc2 == 1
