#!/bin/bash
# chip_smoke.py twice in ONE chip call: cold (empty compile cache), then warm.
#   chiprun --timeout 2400 -- bash scripts/chip_cold_warm.sh
# Phase lines and stderr are kept under chiprun_out/; the cache stays on the
# machine (it is hundreds of MB and is thrown away with it).
mkdir -p chiprun_out
cache="${JAX_COMPILATION_CACHE_DIR:-.jax_cache}"
rm -rf "$cache"
rc=0
for run in cold warm; do
  t0=$SECONDS
  python chip_smoke.py "$@" > "chiprun_out/smoke_$run.out" 2> "chiprun_out/smoke_$run.err"
  code=$?
  [ $code -eq 0 ] || rc=1
  echo "== $run: exit $code, $((SECONDS - t0)) s wall, cache $(du -sm "$cache" 2>/dev/null | cut -f1) MB in $(ls "$cache" 2>/dev/null | wc -l) files"
  cat "chiprun_out/smoke_$run.out"
done
[ $rc -eq 0 ] || tail -n 40 chiprun_out/smoke_cold.err chiprun_out/smoke_warm.err | cut -c1-600
exit $rc
