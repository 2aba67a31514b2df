"""Preflight validation of the staged hardware session (VERDICT r4 #1).

Round 4 lost part of its only 4-minute chip window to flag rot: the staged
t=8k bench line invoked `bench.py --maxlen 8192 --batch_size 2` — flags
bench.py does not have — and round 3's staged kernel-check script had a
sys.path bug. Nothing validated the staged scripts against the real CLIs
before the scarce window opened.

This test extracts EVERY python invocation from runs/r5/*.sh (including
those wrapped in scripts/run_step.py and the bench_line/step shell helpers)
and validates it against the REAL argparser of the target program, on CPU,
in CI. A staged command that would die on argparse now fails the suite
instead of the chip window.
"""

import os
import re
import shlex

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
R5 = os.path.join(REPO, "runs", "r5")

# every staged session dir gets preflighted (r6 stages the fast-45m pass,
# r7 the comm-overlap A/B, r8 the serving loadgen sweep, r9 the paged
# serving-v2 sweep + slot-vs-paged A/B, r10 the speculative k-sweep +
# fused-sampler ablation, r11 the int8 wire sweep + int8-KV serving arms,
# r12 the ZeRO stage x wire ladder + RS/AG breakdown arm, r13 the
# regression-gated trajectory point + traced/flight-recorded serving,
# r14 the live telemetry plane: exported serving + collector rollup +
# the SLO-collapse anomaly arm with cross-linked device profiling,
# r15 the paged-attention kernel: pages_per_block autotune + the
# gather-vs-pallas A/B sweep with int8 and speculative arms,
# r16 measured attribution: duty-cycled profiled train window, the
# measured breakdown + profiled serving bench arms, the anomaly capture
# that parses, and the measured-ms regression gate,
# r17 the control plane: advise-mode train window, act-mode serving
# loadgen with a burst traffic shift, the off-mode zero-cost arm, and
# the check_bench_regression --controller window gate,
# r18 run forensics: the archive index over the real runs, two
# profiled serving arms one knob apart + their pairwise diff, the
# --explain gate on a forced regression, and the triage/trajectory
# passes,
# r19 long-context cp serving: traced cp-contract preflight, the
# cp{1,2} A/B one knob apart, the 32k-token-prompt capacity arm, the
# int8-KV cp arm, and the cp2-vs-cp1 regression-gate line,
# r20 the serving fleet: the live 2-replica router arm + its
# single-replica baseline, the disaggregated prefill->decode arms
# (native + int8 wire), the four-arm bench --fleet A/B, and the
# int8-vs-native fleet regression-gate line,
# r21 elastic reshard: the tp4 training artifact, the offline
# plan-then-reshard to tp2 + serving it, the elastic dp2xtp2 --resume
# arm off the tp4 checkpoint, the fleet width-restart arm, and the
# bench --reshard pair with its regression-gate line)
SESSION_DIRS = [d for d in (R5, os.path.join(REPO, "runs", "r6"),
                            os.path.join(REPO, "runs", "r7"),
                            os.path.join(REPO, "runs", "r8"),
                            os.path.join(REPO, "runs", "r9"),
                            os.path.join(REPO, "runs", "r10"),
                            os.path.join(REPO, "runs", "r11"),
                            os.path.join(REPO, "runs", "r12"),
                            os.path.join(REPO, "runs", "r13"),
                            os.path.join(REPO, "runs", "r14"),
                            os.path.join(REPO, "runs", "r15"),
                            os.path.join(REPO, "runs", "r16"),
                            os.path.join(REPO, "runs", "r17"),
                            os.path.join(REPO, "runs", "r18"),
                            os.path.join(REPO, "runs", "r19"),
                            os.path.join(REPO, "runs", "r20"),
                            os.path.join(REPO, "runs", "r21"))
                if os.path.isdir(d)]
SESSION_SCRIPTS = [os.path.join(d, n)
                   for d in SESSION_DIRS
                   for n in sorted(os.listdir(d)) if n.endswith(".sh")]

# shell variables the session scripts define; substituted before lexing.
# $R/$M are per-script (the sourcing script's runs dir).
SHELL_VARS = {
    "TOKENS": "/tmp/corpus_tokens.json",
    "LOG": "/tmp/tpu_status_r5.txt",
}
REDIRECT = re.compile(r"^\d*(>>?|\|)|^\|\|?$|^&&$|^2>>?$")


def _sub_vars(line: str, rdir: str) -> str:
    subs = dict(SHELL_VARS, R=rdir, M=f"{rdir}/session_manifest.jsonl")
    for k, v in subs.items():
        line = line.replace("${%s}" % k, v).replace("$%s" % k, v)
    return line


def _strip_shell_tail(tokens):
    """Drop everything from the first redirection/pipe onward."""
    out = []
    for i, t in enumerate(tokens):
        if REDIRECT.match(t):
            break
        if t in (">", ">>", "<", "|", "||", "&&", ";"):
            break
        out.append(t)
    return out


def extract_commands(path):
    """Yield (lineno, argv) for every staged python command in a script."""
    text = open(path).read()
    rdir = "runs/" + os.path.basename(os.path.dirname(path))
    # join backslash continuations
    text = re.sub(r"\\\n\s*", " ", text)
    cmds = []
    for lineno, raw in enumerate(text.splitlines(), 1):
        line = _sub_vars(raw.strip(), rdir)
        if not line or line.startswith("#"):
            continue
        # bench_line TAG TIMEOUT flags...  =>  python bench.py flags...
        m = re.match(r"bench_line\s+(\S+)\s+(\S+)\s+(.*)$", line)
        if m:
            toks = _strip_shell_tail(shlex.split(m.group(3)))
            cmds.append((lineno, ["python", "bench.py"] + toks))
            continue
        # step NAME TIMEOUT cmd...  =>  cmd...
        m = re.match(r"step\s+(\S+)\s+(\S+)\s+(python\s.*)$", line)
        if m:
            line = m.group(3)
        if "python" not in line:
            continue
        try:
            toks = shlex.split(line)
        except ValueError:
            continue
        # find EVERY python command on the line (a `summarize && refresh`
        # chain stages two commands; stopping at the first would leave the
        # second unvalidated)
        while "python" in toks:
            i = toks.index("python")
            toks = toks[i:]
            argv = _strip_shell_tail(toks)
            # `python scripts/run_step.py <wrapper flags> -- cmd...`:
            # record the WRAPPER invocation too (its flags must parse — a
            # `--time-out` typo would exit 97 on the chip), then unwrap
            if len(argv) >= 2 and argv[1].endswith("run_step.py"):
                if not any("$" in a for a in argv):
                    cmds.append((lineno, argv))
                if "--" in toks:
                    toks = toks[toks.index("--") + 1:]
                    continue
                break
            if len(argv) >= 2:
                cmds.append((lineno, argv))
            # resume scanning past this command for a chained `&& python ...`
            toks = toks[max(len(argv), 1):]
    # drop function-template lines (contain unexpanded "$@")
    return [(ln, argv) for ln, argv in cmds
            if not any("$" in a for a in argv)]


ALL_COMMANDS = [(os.path.basename(p), ln, argv)
                for p in SESSION_SCRIPTS
                for ln, argv in extract_commands(p)]


def _load_script(name):
    """Import a scripts/*.py file by path (scripts/ is not a package)."""
    import importlib.util
    path = os.path.join(REPO, "scripts", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_staged_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _parse_with(parse_fn, argv):
    try:
        parse_fn(argv)
    except SystemExit as e:
        if e.code not in (0, None):
            pytest.fail(f"argparse rejected staged flags: {argv}")


def validate(argv):
    """Dispatch one extracted command to the matching real parser."""
    prog = argv[1]
    rest = argv[2:]
    if prog == "-c":
        return  # inline probe snippets: syntax-checked below
    if prog == "-m":
        mod, rest = argv[2], argv[3:]
        if mod == "distributed_pytorch_from_scratch_tpu.train":
            from distributed_pytorch_from_scratch_tpu.train import (
                get_train_args)
            return _parse_with(get_train_args, rest)
        if mod == "distributed_pytorch_from_scratch_tpu.evaluate":
            from distributed_pytorch_from_scratch_tpu.evaluate import (
                get_eval_args)
            return _parse_with(get_eval_args, rest)
        if mod == "distributed_pytorch_from_scratch_tpu.data.tokenizer":
            from distributed_pytorch_from_scratch_tpu.data.tokenizer import (
                parse_args)
            return _parse_with(parse_args, rest)
        if mod == "distributed_pytorch_from_scratch_tpu.serving.serve":
            from distributed_pytorch_from_scratch_tpu.serving.serve import (
                get_serve_args)
            return _parse_with(get_serve_args, rest)
        pytest.fail(f"staged module has no registered parser: {mod}")
    # script path
    path = os.path.join(REPO, prog)
    assert os.path.exists(path), f"staged script missing: {prog}"
    if prog == "bench.py":
        import bench
        return _parse_with(bench.parse_args, rest)
    if prog.startswith("scripts/") and prog.endswith(".py"):
        name = os.path.basename(prog)[:-3]
        if name in ("tpu_checks", "make_image_corpus", "tune_flash_blocks",
                    "check_bench_regression", "graftcheck", "obs_top",
                    "obs_diff", "serve_fleet", "reshard_ckpt"):
            mod = _load_script(name)
            return _parse_with(mod.parse_args, rest)
        if name == "run_step":
            return _load_script(name).parse_argv(rest)
    if prog.endswith("scripts/summarize_run.py"):
        assert rest and rest[0].startswith("runs/"), rest
        return
    if prog.endswith("scripts/refresh_baseline.py"):
        assert rest and re.fullmatch(r"runs/r\d+", rest[0]), rest
        return
    pytest.fail(f"staged script has no registered parser: {prog}")


def test_session_scripts_exist():
    assert SESSION_SCRIPTS, "no staged session scripts under runs/r5/"
    names = [os.path.basename(p) for p in SESSION_SCRIPTS]
    assert "run_experiment.sh" in names


def test_commands_were_extracted():
    """The extractor must actually see the session's heavy hitters — an
    extraction regression would otherwise silently validate nothing."""
    flat = [" ".join(argv) for _, _, argv in ALL_COMMANDS]
    assert any("bench.py" in c for c in flat)
    assert any("distributed_pytorch_from_scratch_tpu.train" in c for c in flat)
    assert any("distributed_pytorch_from_scratch_tpu.evaluate" in c
               for c in flat)
    assert any("tpu_checks.py" in c for c in flat)
    assert len(flat) >= 15, flat


@pytest.mark.parametrize(
    "script,lineno,argv", ALL_COMMANDS,
    ids=[f"{s}:{ln}:{' '.join(a[1:3])}" for s, ln, a in ALL_COMMANDS])
def test_staged_command_parses(script, lineno, argv):
    validate(argv)


def test_inline_snippets_compile():
    """`python -c '...'` probe snippets must at least be valid python."""
    for script, lineno, argv in ALL_COMMANDS:
        if argv[1] == "-c" and len(argv) > 2:
            compile(argv[2], f"{script}:{lineno}", "exec")


def test_staged_paths_exist():
    """Every runs/ or scripts/ path mentioned in a staged command must
    exist NOW (a staged script once referenced a file whose bug was only
    discovered on the chip)."""
    for script, lineno, argv in ALL_COMMANDS:
        for tok in argv:
            if tok.startswith(("scripts/", "runs/")) and "." in tok:
                if tok.endswith((".py", ".sh")):
                    assert os.path.exists(os.path.join(REPO, tok)), (
                        f"{script}:{lineno} references missing {tok}")


def test_priority_bench_tags_are_a_subset_of_the_full_session():
    """run_priority.sh's bench lines must exist in the full session (the
    two share artifacts: a later full pass skips what the short one
    landed)."""
    text = open(os.path.join(R5, "run_experiment.sh")).read()
    exp_tags = set(re.findall(r"^bench_line\s+(\S+)", text, re.M))
    text = open(os.path.join(R5, "run_priority.sh")).read()
    pri_tags = set(re.findall(r"^bench_line\s+(\S+)", text, re.M))
    assert exp_tags, "no bench_line calls extracted from run_experiment.sh"
    assert pri_tags <= exp_tags, (
        f"priority-pass tags not in the full session: "
        f"{sorted(pri_tags - exp_tags)}")


def test_train_and_priority_train_flags_agree():
    """run_priority.sh's training slice must resume the SAME run as
    run_experiment.sh: same save_dir, model shape flags, and optimizer
    schedule, else a short-window slice would corrupt the long run."""
    full = priority = None
    for script, lineno, argv in ALL_COMMANDS:
        if "distributed_pytorch_from_scratch_tpu.train" in argv and \
                "runs/r5/ckpt" in argv:
            if script == "run_experiment.sh":
                full = argv
            elif script == "run_priority.sh":
                priority = argv
    assert full and priority
    from distributed_pytorch_from_scratch_tpu.train import get_train_args
    a = get_train_args(full[3:])
    b = get_train_args(priority[3:])
    for field in ("save_dir", "data_path", "batch_size", "maxlen",
                  "max_steps", "warmup_steps", "lr", "steps_per_dispatch",
                  "remat", "save_interval", "lr_schedule", "bf16"):
        assert getattr(a, field) == getattr(b, field), field
