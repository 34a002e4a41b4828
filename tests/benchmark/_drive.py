"""How the benchmark's tests drive a run where there is no chip, in this
process (test_bench_faults.py) or, run as a script, in a child whose harness
is ANOTHER tree's (test_bench_admission.py: a copy of the benchmark with a
configuration of another architecture laid over it, imported as that tree's
own run.py imports itself, so that nothing of this checkout's is mixed in):

    python _drive.py <root> load <workload>
    python _drive.py <root> read <file: {"metrics": [...], "ctx": {...}}>
    python _drive.py <root> run <state_root> <sound|token_altered> <run.py's arguments>

Each prints one JSON object as its last line. Imports nothing of the
harness at the top: the child decides which tree's harness it is."""

import json
import os
import sys


def fake_probe(_peaks, _chips):
    return {"platform": "cpu", "kind": "cpu", "count": 1}


def skip_the_chip_look(bench_run, set_=setattr) -> None:
    """The look for a chip is skipped, and a CPU backend reports no memory
    statistics. `set_` is `monkeypatch.setattr` where the process goes on."""
    set_(bench_run, "probe_device", fake_probe)
    serve = bench_run.serve_and_measure

    def serve_with_a_memory_reading(*args):
        result = serve(*args)
        result["device"]["memory_peak_bytes"] = result["device"]["memory_peak_bytes"] or 1
        return result

    set_(bench_run, "serve_and_measure", serve_with_a_memory_reading)


def alter_a_token(built):
    """Break the program under the harness: the class `llm_service` hands
    back gets a load() that is its own, after which every stream's fourth
    token is off by one at the point where the engine hands it to the
    stream. (The class is local, so it travels to the container by value.)"""
    import modal_tpu

    class AlterToken(built._user_cls):
        @modal_tpu.enter(snap=True)
        def load(self):
            parent = super().load  # a partial here, the bound method in the container
            parent.raw_f(self) if hasattr(parent, "raw_f") else parent()
            from modal_tpu.serving.engine import GenRequest

            original = GenRequest._append

            def altered(req, token):
                original(req, (token + 1) % 512 if len(req.tokens) == 3 else token)

            GenRequest._append = altered

    built._user_cls = AlterToken
    return built


def main(argv: list) -> dict:
    root, what, *rest = argv
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import run as bench_run
    from kernels import counts_for

    assert bench_run.REPO_ROOT == root, (bench_run.REPO_ROOT, root)
    if what == "load":
        try:
            cell = bench_run.load_cell(root, rest[0])
        except bench_run.RunFailed as exc:
            return {"run_failed": str(exc)}
        return {
            "reference": cell["reference"], "counts_file": counts_for(cell["config"]).__file__,
            "end_to_end": sorted(cell["end_to_end"]), "per_layer": sorted(cell["per_layer"]),
        }
    if what == "read":
        with open(rest[0]) as f:
            asked = json.load(f)
        return {name: bench_run.read_layer_metric(name, asked["ctx"]) for name in asked["metrics"]}
    state_root, case, *run_argv = rest
    skip_the_chip_look(bench_run)
    children, run_child = [], bench_run.run_child

    def recording_child(argv, timeout_s, env=None):
        children.append([argv[0], timeout_s])
        run_child(argv, timeout_s, env)

    bench_run.run_child = recording_child
    sys.path.insert(0, root)
    from modal_tpu._utils.async_utils import synchronizer
    from modal_tpu.server.supervisor import LocalSupervisor

    # a control plane of this run's own on a free port, as the tests' `supervisor` fixture
    # is: the port `app.run()` boots one on by itself is another test's too
    sup = LocalSupervisor(num_workers=1, state_dir=os.path.join(state_root, "supervisor"), worker_chips=8, worker_tpu_type="local-sim")
    synchronizer.run(sup.start())
    os.environ["MODAL_TPU_SERVER_URL"] = f"grpc://127.0.0.1:{sup.port}"
    if case == "token_altered":
        import modal_tpu.serving

        real = modal_tpu.serving.llm_service
        modal_tpu.serving.llm_service = lambda *a, **kw: alter_a_token(real(*a, **kw))
    try:
        line = bench_run.measure(bench_run.parse(run_argv), state_root=state_root)
    finally:
        synchronizer.run(sup.stop())
    return {"line": line, "children": children}


if __name__ == "__main__":
    print(json.dumps(main(sys.argv[1:])))
