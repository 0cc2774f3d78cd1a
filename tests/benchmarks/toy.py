"""A throw-away benchmark tree at toy sizes, made of files only.

The real harness (``benchmarks/run.py`` and ``benchmarks/lib``) runs it
through ``--root``; nothing in the harness knows these names. That is
the proof that a later PR can add a configuration, a traffic mix, a
cell and a per-layer metric as new files and new entries. With
``second_family`` the tree also holds a configuration of another model
family (``model_type: "llama"``: no q/k norm, a head of its own, MHA)
with its own plain reference, ``toy_llama_reference.py`` copied into
the tree, and a train and a serve cell on it: the family too is files
and entries only.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TOY_MODEL = {
    "model_type": "qwen3", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000, "tie_word_embeddings": True,
    "reduced": [], "assumed": {},
    "reference": "benchmarks/reference/qwen3.py",
}

TOY_LLAMA_REFERENCE = "benchmarks/reference/toy_llama.py"
TOY_LLAMA = {
    "model_type": "llama", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "reduced": [], "assumed": {},
    "reference": TOY_LLAMA_REFERENCE,
}
# family -> (configuration keys, prefix of its configurations and cells)
FAMILIES = {"qwen3": (TOY_MODEL, "toy"), "llama": (TOY_LLAMA, "toy-llama")}


CLIENT_SIDE_READERS = (
    ("serve_ttft_p50_ms", "ms"), ("serve_ttft_p90_ms", "ms"),
    ("serve_tokens_per_s", "tokens/s"), ("serve_loadgen_late_p95_ms", "ms"),
    ("serve_queue_wait_p95_ms", "ms"),
)


def _write(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_toy_root(root: str, *, cp: int = 1, serve_kind: str = "closed_loop",
                  extra_metric: bool = False, second_family: bool = False,
                  references: dict = None, serve_rtol_of_max: float = None,
                  ) -> str:
    """Writes the tree and returns ``root``. Cells: ``toy-train`` (cp
    chips), ``toy-serve`` (one chip); with ``second_family`` also
    ``toy-llama-train`` and ``toy-llama-serve``. ``references`` points a
    family's configurations at another reference file;
    ``serve_rtol_of_max`` states the serve cells' own tolerance (float32
    at toy size lands four orders under the 1.7B cell's bf16 one)."""
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks", "metrics"),
                    os.path.join(bench, "metrics"))
    shutil.copy(os.path.join(REPO, "benchmarks", "peaks.json"), bench)
    families = ["qwen3"] + (["llama"] if second_family else [])
    if second_family:
        os.makedirs(os.path.join(bench, "reference"))
        shutil.copy(os.path.join(REPO, "tests", "benchmarks",
                                 "toy_llama_reference.py"),
                    os.path.join(root, TOY_LLAMA_REFERENCE))
    for family in families:
        model, prefix = FAMILIES[family]
        model = dict(model, reference=(references or {}).get(
            family, model["reference"]))
        _write(os.path.join(bench, "configs", f"{prefix}-train.json"), dict(
            model, name=f"{prefix}-train", source="made up for the tests",
            train={"dtype": "bfloat16", "param_dtype": "float32",
                   "gradient_checkpointing": True}))
        _write(os.path.join(bench, "configs", f"{prefix}-serve.json"), dict(
            model, name=f"{prefix}-serve", source="made up for the tests",
            serve={"max_slots": 4, "max_seq": 128, "prefill_len": 64,
                   "page_size": 16, "dtype": "float32"}))
    _write(os.path.join(bench, "traffic", "toy-steps.json"), {
        "kind": "train_steps", "sequence_length": 64 * cp,
        "sequences_per_step": 1, "distinct_batches": 2})
    sizes = {"prompt_tokens": {"dist": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 8, "max": 48},
             "max_new_tokens": {"dist": "uniform", "min": 12, "max": 24}}
    if serve_kind == "closed_loop":
        serve_traffic = dict(sizes, kind="closed_loop", clients=4,
                             requests_per_client=400, lead_in_s=0.5)
    else:
        serve_traffic = dict(sizes, kind="open_loop_stratified",
                             rate_per_s=8.0, lead_in_s=0.5)
    _write(os.path.join(bench, "traffic", "toy-requests.json"),
           serve_traffic)
    launch = {}
    if cp > 1:
        launch = {"context_parallel_size": cp, "attention_backend": "ring",
                  "cp_layout": "zigzag"}
    serve_check = {"prompts": 4, "decode_positions": 8, "q_block": 8}
    if serve_rtol_of_max is not None:
        serve_check["rtol_of_max"] = serve_rtol_of_max
    prefixes = [FAMILIES[f][1] for f in families]
    for prefix in prefixes:
        _write(os.path.join(bench, "workloads", f"{prefix}-train.json"), {
            "name": f"{prefix}-train", "kind": "train",
            "config": f"{prefix}-train", "traffic": "toy-steps",
            "chips": cp, "launch": launch,
            # 64 positions over a 512-word vocabulary average bf16
            # rounding far less than 8192 over 151,936 do: the toy
            # cell's own tolerance
            "check": {"gradients": True, "q_block": 32, "loss_chunk": 32,
                      "loss_rtol": 5e-4, "grad_norm_rtol": 8e-3,
                      "gain_grad_rtol": 5e-2},
            "warmup_steps": 1, "trace_seconds": 0.5})
        _write(os.path.join(bench, "workloads", f"{prefix}-serve.json"), {
            "name": f"{prefix}-serve", "kind": "serve",
            "config": f"{prefix}-serve", "traffic": "toy-requests",
            "chips": 1, "check": serve_check, "trace_seconds": 0.5})

    def toy_cells(real_cells):
        """The toy cells that stand for these cells of BENCHMARK.json:
        the one change to an existing line that a new cell needs is its
        name in the ``workloads`` list of each metric it reports."""
        kinds = {"train" if c.startswith("train") else "serve"
                 for c in real_cells}
        return sorted(f"{p}-{k}" for p in prefixes for k in kinds)

    per_layer = []
    index = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for metric in index["per_layer"]:
        per_layer.append(dict(
            metric, workloads=toy_cells(metric.get("workloads", []))))
    # readers that are files under benchmarks/metrics and in no cell of
    # BENCHMARK.json yet (client-side quantities waiting for an
    # end-to-end metric they move): the toy tree adds them as entries
    # only, which is how a later PR will
    for name, unit in CLIENT_SIDE_READERS:
        per_layer.append({
            "name": name, "unit": unit,
            "better": "higher" if unit == "tokens/s" else "lower",
            "source": "host_clock", "layer": "client",
            "moves": "serve_itl_p99_ms",
            "workloads": [f"{p}-serve" for p in prefixes]})
    if extra_metric:
        # counters as files: the runner's own count, and two of the
        # program's own (a scalar of the last step's metrics, a number
        # of the engine's snapshot as its change over the window)
        for name, key, unit, kind, moves in (
                ("toy_steps_counted", "steps", "steps", "train",
                 "train_tokens_per_s_per_chip"),
                ("toy_step_loss", "step.loss", "nats", "train",
                 "train_tokens_per_s_per_chip"),
                ("toy_engine_decode_steps", "engine.decode_steps", "steps",
                 "serve", "serve_itl_p99_ms")):
            _write(os.path.join(bench, "metrics", f"{name}.json"), {
                "name": name, "reducer": {"kind": "counter", "key": key}})
            per_layer.append({
                "name": name, "unit": unit, "better": "higher",
                "source": "program_counter", "layer": f"{kind} step",
                "moves": moves,
                "workloads": [f"{p}-{kind}" for p in prefixes]})
    end_to_end = []
    for metric in index["end_to_end"]:
        if "workloads" in metric:
            metric = dict(metric, workloads=toy_cells(metric["workloads"]))
        end_to_end.append(metric)
    _write(os.path.join(root, "BENCHMARK.json"), {
        "command": index["command"], "paths": index["paths"],
        "run_seconds": 1,
        "configs": [
            {"name": f"{p}-{k}", "source": "made up for the tests",
             "file": f"benchmarks/configs/{p}-{k}.json", "reduced": [],
             "why": "toy"} for p in prefixes for k in ("train", "serve")],
        "workloads": [
            cell for p in prefixes for cell in (
                {"name": f"{p}-train", "config": f"{p}-train",
                 "traffic": "toy-steps", "chips": cp, "why": "toy"},
                {"name": f"{p}-serve", "config": f"{p}-serve",
                 "traffic": "toy-requests", "chips": 1, "why": "toy"})],
        "end_to_end": end_to_end, "per_layer": per_layer,
    })
    return root
