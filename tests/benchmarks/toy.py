"""A throw-away benchmark tree at toy sizes, made of files only.

The real harness (``benchmarks/run.py`` and ``benchmarks/lib``) runs it
through ``--root``; nothing in the harness knows these names. That is
the proof that a later PR can add a configuration, a traffic mix, a
cell and a per-layer metric as new files and new entries.
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
}


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
                  extra_metric: bool = False) -> str:
    """Writes the tree and returns ``root``. Cells: ``toy-train`` (cp
    chips), ``toy-serve`` (one chip)."""
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks", "metrics"),
                    os.path.join(bench, "metrics"))
    shutil.copy(os.path.join(REPO, "benchmarks", "peaks.json"), bench)
    _write(os.path.join(bench, "configs", "toy-train.json"), dict(
        TOY_MODEL, name="toy-train", source="made up for the tests",
        train={"dtype": "bfloat16", "param_dtype": "float32",
               "gradient_checkpointing": True}))
    _write(os.path.join(bench, "configs", "toy-serve.json"), dict(
        TOY_MODEL, name="toy-serve", source="made up for the tests",
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
    _write(os.path.join(bench, "workloads", "toy-train.json"), {
        "name": "toy-train", "kind": "train", "config": "toy-train",
        "traffic": "toy-steps", "chips": cp, "launch": launch,
        # 64 positions over a 512-word vocabulary average bf16 rounding
        # far less than 8192 over 151,936 do: the toy cell's own tolerance
        "check": {"gradients": True, "q_block": 32, "loss_chunk": 32,
                  "loss_rtol": 5e-4, "grad_norm_rtol": 8e-3,
                  "gain_grad_rtol": 5e-2},
        "warmup_steps": 1, "trace_seconds": 0.5})
    _write(os.path.join(bench, "workloads", "toy-serve.json"), {
        "name": "toy-serve", "kind": "serve", "config": "toy-serve",
        "traffic": "toy-requests", "chips": 1,
        "check": {"prompts": 4, "decode_positions": 8, "q_block": 8},
        "trace_seconds": 0.5})
    per_layer = []
    index = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for metric in index["per_layer"]:
        cells = metric.get("workloads", [])
        kinds = {("toy-train" if c.startswith("train") else "toy-serve")
                 for c in cells}
        per_layer.append(dict(metric, workloads=sorted(kinds)))
    # readers that are files under benchmarks/metrics and in no cell of
    # BENCHMARK.json yet (client-side quantities waiting for an
    # end-to-end metric they move): the toy tree adds them as entries
    # only, which is how a later PR will
    for name, unit in CLIENT_SIDE_READERS:
        per_layer.append({
            "name": name, "unit": unit,
            "better": "higher" if unit == "tokens/s" else "lower",
            "source": "host_clock", "layer": "client",
            "moves": "serve_itl_p95_ms", "workloads": ["toy-serve"]})
    if extra_metric:
        _write(os.path.join(bench, "metrics", "toy_steps_counted.json"), {
            "name": "toy_steps_counted",
            "reducer": {"kind": "counter", "key": "steps"}})
        per_layer.append({
            "name": "toy_steps_counted", "unit": "steps",
            "better": "higher", "source": "program_counter",
            "layer": "train step",
            "moves": "train_tokens_per_s_per_chip",
            "workloads": ["toy-train"]})
    end_to_end = []
    for metric in index["end_to_end"]:
        if "workloads" in metric:
            kinds = {("toy-train" if c.startswith("train") else "toy-serve")
                     for c in metric["workloads"]}
            metric = dict(metric, workloads=sorted(kinds))
        end_to_end.append(metric)
    _write(os.path.join(root, "BENCHMARK.json"), {
        "command": index["command"], "paths": index["paths"],
        "run_seconds": 1,
        "configs": [
            {"name": n, "source": "made up for the tests",
             "file": f"benchmarks/configs/{n}.json", "reduced": [],
             "why": "toy"} for n in ("toy-train", "toy-serve")],
        "workloads": [
            {"name": "toy-train", "config": "toy-train",
             "traffic": "toy-steps", "chips": cp, "why": "toy"},
            {"name": "toy-serve", "config": "toy-serve",
             "traffic": "toy-requests", "chips": 1, "why": "toy"}],
        "end_to_end": end_to_end, "per_layer": per_layer,
    })
    return root
