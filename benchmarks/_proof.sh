cd .bench_copy || exit 1
ls -a | head -50
python3 -m benchmarks.run --workload mistral-7b.train-steady --seed 2147483659 --seconds 10 --trace 0 2> ../chiprun_out/proof1.err | tail -2 | cut -c1-900; echo rc=$?
python3 -m benchmarks.run --workload mistral-7b.train-flashsave --seed 2147483659 --seconds 12 --trace 1 2> ../chiprun_out/proof2.err | tail -1 | cut -c1-1500; echo rc=$?
for f in ../chiprun_out/proof1.err ../chiprun_out/proof2.err; do tail -n 3 $f | cut -c1-300; done
ls -a . | head -40; ls /dev/shm
