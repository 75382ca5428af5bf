#!/usr/bin/env bash
# Pre-PR gate: the orion_tpu.analysis static-analysis suite over the
# whole tree — per-file rules AND the project phase (lock-discipline /
# frame-exhaustive / config-drift), which needs the full path set in
# ONE invocation to see every cross-file reader.  Nonzero exit on any
# unsuppressed finding — run this before every PR
# (tests/test_analysis.py enforces the same cleanliness in tier-1, so
# a dirty tree fails CI either way).
#
#   bash scripts/lint.sh                       # analyze the default tree
#   bash scripts/lint.sh --no-project mydir/   # partial-path run: the
#                                              # project rules judge the
#                                              # WHOLE tree, so skip
#                                              # their findings here
#   bash scripts/lint.sh --format sarif        # CI-ingestible output
#   bash scripts/lint.sh --baseline b.json     # warn-first landing
#   bash scripts/lint.sh --no-cache            # bypass the result cache
#   bash scripts/lint.sh --changed             # per-file phase only on
#                                              # files changed vs
#                                              # `git merge-base HEAD
#                                              # main` (project phase
#                                              # still full-tree)
#   bash scripts/lint.sh --stats               # one-line perf summary
#                                              # (rules/findings/cache
#                                              # hit rate/wall) on
#                                              # stderr
#   bash scripts/lint.sh --fix-suppressions    # delete stale
#                                              # `# orion: ignore` comments
#
# Flags (anything starting with "-") pass straight through to
# `python -m orion_tpu.analysis`; positional args REPLACE the default
# path set.  The content-hash result cache is on by default
# (~/.cache/orion-tpu-analysis-<cwd>.json) — only changed files re-run
# the per-file rules; the project phase always runs fresh.
set -euo pipefail
cd "$(dirname "$0")/.."

flags=()
paths=()
for arg in "$@"; do
    case "$arg" in
        -*) flags+=("$arg") ;;
        *)
            # a flag VALUE (e.g. the file after --baseline) rides with
            # the flags when the previous arg expects one
            if [ "${#flags[@]}" -gt 0 ]; then
                case "${flags[${#flags[@]}-1]}" in
                    --baseline|--cache|--format|--rule)
                        flags+=("$arg"); continue ;;
                esac
            fi
            paths+=("$arg") ;;
    esac
done
if [ "${#paths[@]}" -eq 0 ]; then
    paths=(orion_tpu tests scripts __graft_entry__.py)
fi
# ${arr[@]+...} guards the empty-array expansion: under `set -u`,
# bash < 4.4 treats a bare "${flags[@]}" on an empty array as unbound.
exec python -m orion_tpu.analysis ${flags[@]+"${flags[@]}"} \
    ${paths[@]+"${paths[@]}"}
