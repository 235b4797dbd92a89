"""End-to-end system behaviour tests: the paper's workflow (allocate →
fragment → heterogeneous task graph → RIMMS policy) and the framework
workflow (pipeline → train → checkpoint → serve) glued together."""

import dataclasses

import numpy as np

from repro.apps.radar import build_sar, make_session
from repro.core.hete import hete_sync
from session_tasks import submit_tasks


def test_paper_end_to_end_sar():
    """SAR (two-phase FZF) through both policies: same numerics, fewer
    copies under RIMMS, on a 2-accelerator SoC."""
    outs = {}
    copies = {}
    for policy in ("reference", "rimms"):
        with make_session(policy=policy, scheduler="round_robin",
                          accelerators=("fft_acc0", "zip_acc0")) as s:
            bufs, tasks = build_sar(s.context, scale=64, seed=11)  # 8-way + 4-way
            submit_tasks(s, tasks)
            outs[policy] = hete_sync(bufs["phase1"]["out"][1][0],
                                     context=s.context).copy()
            copies[policy] = s.ledger.total_copies
        s.runtime.close()
    np.testing.assert_allclose(outs["reference"], outs["rimms"], atol=1e-4)
    assert copies["rimms"] < copies["reference"]


def test_framework_end_to_end_train_then_serve(tmp_path):
    """Train a tiny LM for a few steps (checkpointed), restore the params
    and serve a request with the paged engine — full lifecycle."""

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.engine import ServeEngine
    from repro.train.checkpoint import restore_checkpoint
    from repro.train.loop import Trainer, TrainerConfig

    cfg = dataclasses.replace(get_config("llama3_8b").smoke(),
                              dtype="float32")
    trainer = Trainer(cfg, batch_size=2, seq_len=16,
                      tcfg=TrainerConfig(steps=3, ckpt_every=3,
                                         ckpt_dir=str(tmp_path)))
    report = trainer.run()
    assert report["final_step"] == 3

    model = build_model(cfg)
    like = {"params": trainer.params, "opt": trainer.opt_state}
    restored, step, _ = restore_checkpoint(tmp_path, like)
    assert step == 3
    eng = ServeEngine(cfg, restored["params"], max_batch=2)
    req = eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run()
    assert req.done and len(req.generated) == 3
    assert all(0 <= t < cfg.vocab for t in req.generated)
