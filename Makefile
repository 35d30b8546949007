# tpulab build/test targets (reference Makefile/build.sh analog).
PY ?= python

.PHONY: all native test test-native test-native-tsan bench-native \
        dryrun smoke engine clean

all: native test

native:
	cmake -S cpp -B cpp/build -G Ninja
	ninja -C cpp/build

test:
	$(PY) -m pytest tests/ -q

test-native: native
	./cpp/build/test_native

# race detection for the native core (beyond-reference: trtlab wires no
# sanitizers); clean run = futex mutex / pools / thread pool race-free
test-native-tsan:
	cmake -S cpp -B cpp/build-tsan -G Ninja -DTPULAB_TSAN=ON
	ninja -C cpp/build-tsan test_native_tsan
	./cpp/build-tsan/test_native_tsan

bench-native: native
	./cpp/build/bench_native

dryrun:
	$(PY) __graft_entry__.py 8

# needs the chip (exits 2 without one): chiprun -- make smoke
smoke:
	$(PY) chip_smoke.py

engine:
	$(PY) tools/build_engine.py --model resnet50 --uint8 \
	    --max-batch 128 --out engines/rn50

clean:
	rm -rf cpp/build cpp/build-tsan .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +
