"""End-to-end driver (the paper's kind: INFERENCE): post-training-quantize
Mixtral-8x7B at its CPU-sized smoke shape to fine-grained W4A8 with
Integer Scale, then serve batched requests through the continuous-batching
engine.

    PYTHONPATH=src python examples/quantize_then_serve.py [--algo gptq]

Prints per-request generations, engine throughput, and the greedy-token
agreement between the Integer-Scale and Float-Scale deployments (the
paper's free-lunch claim, measured end to end on this machine).
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.core import ptq  # noqa: E402
from repro.core.recipe import QuantRecipe, QuantSpec  # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticPipeline  # noqa: E402
from repro.launch.serve import load_model  # noqa: E402
from repro.serving.engine import Engine, ServeConfig  # noqa: E402

ARCH = "mixtral-8x7b"


def build_engine(api, cfg, params, recipe, max_slots=4):
    sc = ServeConfig(max_slots=max_slots, max_seq=128, prefill_len=32,
                     max_new_tokens=24)
    return Engine(api, cfg, params, sc, recipe=recipe)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="rtn",
                    choices=["rtn", "gptq", "awq", "smoothquant"])
    ap.add_argument("--requests", type=int, default=6)
    args = ap.parse_args()

    api, cfg, params = load_model(ARCH, smoke=True)
    print(f"[serve] model={cfg.name}")
    cal = [SyntheticPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=128,
        batch_size=8)).global_batch(50_000)]
    pipe = SyntheticPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                        seq_len=32, batch_size=1))
    prompts = [pipe.batch(200_000 + i)["tokens"][0].tolist()
               for i in range(args.requests)]

    outputs = {}
    for name, mode in (("integer-scale", "integer"), ("float-scale",
                                                      "float")):
        spec = QuantSpec(algo=args.algo, scale_mode=mode)
        recipe = QuantRecipe(rules=(("*", spec),), name=f"{args.algo}-{mode}")
        t0 = time.time()
        qparams = ptq.post_training_quantize(api, cfg, params, recipe, cal)
        t_q = time.time() - t0
        eng = build_engine(api, cfg, qparams, recipe)
        for p in prompts:
            eng.submit(p)
        t0 = time.time()
        outs = eng.run()
        dt = time.time() - t0
        toks = sum(len(v) for v in outs.values())
        print(f"[serve] {name:14s} quantize={t_q:.1f}s "
              f"decode_ticks={eng.ticks} generated={toks} tok "
              f"({toks/dt:.1f} tok/s CPU)")
        outputs[name] = outs

    agree = 0
    total = 0
    for rid in outputs["integer-scale"]:
        a = outputs["integer-scale"][rid]
        b = outputs["float-scale"].get(rid, [])
        n = min(len(a), len(b))
        agree += sum(x == y for x, y in zip(a[:n], b[:n]))
        total += n
    print(f"[serve] IS-vs-FS greedy agreement: {agree}/{total} "
          f"({100*agree/max(total,1):.1f}%) — the free lunch, end to end")
    for rid, toks in sorted(outputs["integer-scale"].items())[:3]:
        print(f"[serve] request {rid}: {toks}")


if __name__ == "__main__":
    main()
