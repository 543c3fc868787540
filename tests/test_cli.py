"""train.py CLI: flag parsing -> Config mapping (reference train.py:27-31
flags + the hard-coded constants as defaults)."""

import train as cli


def test_reference_defaults_map_to_config():
    args = cli.build_parser().parse_args(["--datadir", "/d"])
    cfg = cli.config_from_args(args)
    assert cfg.data.data_dir == "/d"
    assert cfg.data.batch_size == 4          # train.py:30
    assert cfg.data.resize_size == 299       # train.py:110
    assert cfg.optim.learning_rate == 0.5e-5  # train.py:127
    assert tuple(cfg.optim.milestones) == (50, 80)  # train.py:156
    assert cfg.optim.class_weights == (3, 3, 10, 1, 4, 4, 5)  # train.py:157
    assert cfg.run.epochs == 100             # train.py:161
    assert cfg.run.ckpt_dir == "dtmodel/cp"  # train.py:136
    assert cfg.run.save_period == 5          # train.py:183
    assert cfg.data.num_workers == 6         # train.py:114


def test_local_rank_accepted_for_compat():
    # reference launch command passes --local_rank (README.md:6, train.py:28)
    args = cli.build_parser().parse_args(
        ["--datadir", "/d", "--local_rank", "3"])
    assert args.local_rank == 3


def test_no_class_weights_flag():
    args = cli.build_parser().parse_args(
        ["--datadir", "/d", "--no-class-weights"])
    assert cli.config_from_args(args).optim.class_weights == ()


def test_empty_milestones():
    args = cli.build_parser().parse_args(["--datadir", "/d", "--milestones"])
    assert cli.config_from_args(args).optim.milestones == ()


def test_class_weights_auto_and_numeric():
    import train as cli
    p = cli.build_parser()
    a = p.parse_args(["--datadir", "/d", "--class-weights", "auto"])
    cfg = cli.config_from_args(a)
    assert cfg.optim.auto_class_weights and cfg.optim.class_weights == ()
    a = p.parse_args(["--datadir", "/d", "--class-weights", "1", "2.5"])
    cfg = cli.config_from_args(a)
    assert not cfg.optim.auto_class_weights
    assert cfg.optim.class_weights == (1.0, 2.5)
    a = p.parse_args(["--datadir", "/d"])  # reference default vector intact
    cfg = cli.config_from_args(a)
    assert cfg.optim.class_weights == (3.0, 3.0, 10.0, 1.0, 4.0, 4.0, 5.0)
    a = p.parse_args(["--datadir", "/d", "--no-class-weights"])
    assert cli.config_from_args(a).optim.class_weights == ()


def test_class_weights_bad_token_clean_error():
    import pytest
    args = cli.build_parser().parse_args(
        ["--datadir", "/d", "--class-weights", "auto", "2"])
    with pytest.raises(SystemExit, match="class-weights"):
        cli.config_from_args(args)


def test_extended_flags_map_to_config():
    args = cli.build_parser().parse_args(
        ["--datadir", "/d", "--val-batchsize", "8", "--prefetch", "3",
         "--device-cache-mb", "0", "--log-every-steps", "10",
         "--label-smoothing", "0.1", "--fused-loss",
         "--clip-grad-norm", "1.0", "--remat", "--remat-policy",
         "attention", "--per-class-metrics"])
    cfg = cli.config_from_args(args)
    assert cfg.data.val_batch_size == 8
    assert cfg.data.prefetch == 3
    assert cfg.data.device_cache_mb == 0
    assert cfg.run.log_every_steps == 10
    assert cfg.optim.label_smoothing == 0.1
    assert cfg.optim.fused_loss
    assert cfg.optim.grad_clip_norm == 1.0
    assert cfg.model.remat and cfg.model.remat_policy == "attention"
    assert cfg.run.per_class_metrics
    # defaults unchanged
    cfg0 = cli.config_from_args(cli.build_parser().parse_args(
        ["--datadir", "/d"]))
    assert cfg0.data.device_cache_mb == 4096
    assert cfg0.run.log_every_steps == 50
    assert not cfg0.optim.fused_loss


def test_no_augment_flag():
    # Default keeps the reference's always-on train-fold chain
    # (dp/loader.py:63-83); --no-augment turns it off for
    # orientation-sensitive datasets (digits: rot90/flip alias 6<->9).
    args = cli.build_parser().parse_args(["--datadir", "/d"])
    assert cli.config_from_args(args).data.augment is True
    args = cli.build_parser().parse_args(["--datadir", "/d", "--no-augment"])
    assert cli.config_from_args(args).data.augment is False
