// tpuinfo: host-side TPU chip enumeration + HBM telemetry shim.
//
// The reference framework delegates accelerator identity/telemetry to NVML /
// `nvidia-smi` (reference: pkg/server/requester/coordination/server.go:55,100,
// inference_server/launcher/gputranslator.py:25). There is no TPU equivalent
// of "nvidia-smi for another process's HBM", so this shim is the one native
// component the TPU build must author itself (SURVEY.md §2.9, §7).
//
// C ABI (consumed by llm_d_fast_model_actuation_tpu/native/tpuinfo.py over
// ctypes):
//   const char* tpuinfo_query(void);   // malloc'd JSON document, caller frees
//   void        tpuinfo_free(void*);
//
// JSON shape:
//   {"chips": [{"chip_id": str, "index": int, "pci_addr": str,
//               "coords": [x,y,z], "total_hbm_bytes": int,
//               "hbm_used_bytes": int}...],
//    "topology": "2x4" | "" , "source": "pci"|"pci+vfio"|"devfs"|"mock"}
//
// Enumeration sources, highest priority first:
//   1. mock: FMA_TPUINFO_MOCK_JSON (verbatim document) or
//      FMA_TPUINFO_MOCK_COUNT=N (synthesized chips) — the hardware-free
//      test path;
//   2. PCI sysfs: /sys/bus/pci/devices/*/vendor == 0x1ae0 (Google). The
//      device id keys a generation table for total HBM. sysfs lists every
//      function of the machine, also inside a container that was handed
//      only some of them: Cloud TPU v5e hosts bind chips to vfio-pci and a
//      process opens a chip through /dev/vfio/<iommu group>. Where
//      /dev/vfio exists, only chips whose group node exists are reported
//      (source "pci+vfio") — the set, and the order, libtpu indexes with
//      TPU_VISIBLE_DEVICES;
//   3. devfs: /dev/accel<N> nodes (one per chip on Cloud TPU VMs).
//
// HBM usage: the TPU runtime does not expose per-process device memory to
// other processes, so usage is a *cooperative* protocol: each engine process
// publishes its live per-chip usage as a decimal byte count in
//   $FMA_TPUINFO_USAGE_DIR/<chip_id>/<pid>        (default /run/fma-tpu/hbm)
// and the shim sums the files of live pids per chip, pruning dead writers by
// probing /proc/<pid>. The engine side writes these files on every
// alloc/sleep/wake transition (engine/sleep.py).

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <dirent.h>
#include <string>
#include <sys/stat.h>
#include <sys/types.h>
#include <unistd.h>
#include <algorithm>
#include <vector>

namespace {

struct Chip {
  std::string chip_id;
  int index = 0;
  std::string pci_addr;
  std::vector<int> coords;  // row-major position in the topology grid
  uint64_t total_hbm = 0;
  uint64_t used_hbm = 0;
};

std::string getenv_str(const char* name) {
  const char* v = ::getenv(name);
  return v ? std::string(v) : std::string();
}

bool read_file(const std::string& path, std::string* out) {
  FILE* f = ::fopen(path.c_str(), "r");
  if (!f) return false;
  char buf[4096];
  size_t n = ::fread(buf, 1, sizeof(buf) - 1, f);
  ::fclose(f);
  buf[n] = '\0';
  out->assign(buf, n);
  return true;
}

std::vector<std::string> list_dir(const std::string& path) {
  std::vector<std::string> names;
  DIR* d = ::opendir(path.c_str());
  if (!d) return names;
  while (dirent* e = ::readdir(d)) {
    if (e->d_name[0] == '.') continue;
    names.emplace_back(e->d_name);
  }
  ::closedir(d);
  std::sort(names.begin(), names.end());
  return names;
}

uint64_t parse_u64(const std::string& s) {
  return ::strtoull(s.c_str(), nullptr, 0);
}

// Google TPU PCI device ids -> (name, HBM bytes per chip).
struct Gen { uint16_t dev; const char* name; uint64_t hbm; };
constexpr uint64_t GiB = 1ull << 30;
const Gen kGens[] = {
    {0x0027, "v2", 8 * GiB},    {0x0056, "v3", 16 * GiB},
    {0x005e, "v4", 32 * GiB},   {0x0063, "v5e", 16 * GiB},
    {0x0062, "v5p", 95 * GiB},  {0x006f, "v6e", 32 * GiB},
};

const Gen* gen_for(uint16_t dev) {
  for (const auto& g : kGens)
    if (g.dev == dev) return &g;
  return nullptr;
}

// --- HBM usage: cooperative drop-file protocol --------------------------

bool pid_alive(const std::string& pid) {
  std::string p = "/proc/" + pid;
  struct stat st;
  return ::stat(p.c_str(), &st) == 0;
}

uint64_t usage_for_chip(const std::string& usage_dir, const std::string& chip_id) {
  uint64_t total = 0;
  std::string dir = usage_dir + "/" + chip_id;
  for (const auto& pid : list_dir(dir)) {
    std::string content;
    if (!read_file(dir + "/" + pid, &content)) continue;
    // Writers name files by pid; skip (and lazily prune) dead writers.
    if (!pid.empty() && pid.find_first_not_of("0123456789") == std::string::npos &&
        !pid_alive(pid)) {
      ::unlink((dir + "/" + pid).c_str());
      continue;
    }
    total += parse_u64(content);
  }
  return total;
}

// --- enumeration sources -------------------------------------------------

std::string dev_root() {
  std::string dev = getenv_str("FMA_TPUINFO_DEV_ROOT");
  return dev.empty() ? "/dev" : dev;
}

bool path_exists(const std::string& path) {
  struct stat st;
  return ::stat(path.c_str(), &st) == 0;
}

// The iommu group of a PCI function ("" when it has none): the basename of
// the .../iommu_group symlink.
std::string iommu_group(const std::string& pci_dir) {
  char buf[512];
  ssize_t n = ::readlink((pci_dir + "/iommu_group").c_str(), buf, sizeof(buf) - 1);
  if (n <= 0) return "";
  std::string target(buf, static_cast<size_t>(n));
  size_t slash = target.find_last_of('/');
  return slash == std::string::npos ? target : target.substr(slash + 1);
}

std::vector<Chip> enumerate_pci(bool* vfio_filtered) {
  std::vector<Chip> chips;
  const std::string root =
      getenv_str("FMA_TPUINFO_SYSFS_ROOT").empty()
          ? "/sys/bus/pci/devices"
          : getenv_str("FMA_TPUINFO_SYSFS_ROOT");
  const std::string vfio = dev_root() + "/vfio";
  *vfio_filtered = path_exists(vfio);
  for (const auto& addr : list_dir(root)) {
    std::string vendor;
    if (!read_file(root + "/" + addr + "/vendor", &vendor)) continue;
    if (parse_u64(vendor) != 0x1ae0) continue;  // Google
    if (*vfio_filtered) {
      std::string group = iommu_group(root + "/" + addr);
      if (group.empty() || !path_exists(vfio + "/" + group)) continue;
    }
    std::string device;
    read_file(root + "/" + addr + "/device", &device);
    const Gen* g = gen_for(static_cast<uint16_t>(parse_u64(device)));
    Chip c;
    c.pci_addr = addr;
    c.total_hbm = g ? g->hbm : 0;
    c.chip_id = std::string("tpu-") + (g ? g->name : "unknown") + "-" + addr;
    chips.push_back(std::move(c));
  }
  return chips;
}

std::vector<Chip> enumerate_devfs() {
  std::vector<Chip> chips;
  const std::string dev = dev_root();
  std::vector<int> ids;
  for (const auto& name : list_dir(dev)) {
    if (name.rfind("accel", 0) == 0 && name.size() > 5 &&
        name.find_first_not_of("0123456789", 5) == std::string::npos) {
      ids.push_back(::atoi(name.c_str() + 5));
    }
  }
  std::sort(ids.begin(), ids.end());
  for (int id : ids) {
    Chip c;
    c.chip_id = "tpu-accel-" + std::to_string(id);
    chips.push_back(std::move(c));
  }
  return chips;
}

std::vector<Chip> enumerate_mock(int count) {
  std::vector<Chip> chips;
  for (int i = 0; i < count; ++i) {
    Chip c;
    c.chip_id = "mock-chip-" + std::to_string(i);
    c.total_hbm = 16 * GiB;
    chips.push_back(std::move(c));
  }
  return chips;
}

// Default topology string for n chips: prefer an Rx4 grid (v5e host layout).
std::string default_topology(size_t n) {
  if (n >= 8 && n % 4 == 0) return std::to_string(n / 4) + "x4";
  if (n == 4) return "2x2";
  return n ? std::to_string(n) : "";
}

// "2x4" -> {2, 4}. Empty/garbage -> {}.
std::vector<int> parse_dims(const std::string& topo) {
  std::vector<int> dims;
  size_t pos = 0;
  while (pos < topo.size()) {
    size_t next = topo.find('x', pos);
    std::string part = topo.substr(pos, next == std::string::npos ? next : next - pos);
    int v = ::atoi(part.c_str());
    if (v <= 0) return {};
    dims.push_back(v);
    if (next == std::string::npos) break;
    pos = next + 1;
  }
  return dims;
}

// Row-major unravel of `i` over `dims` — must agree with the Python model
// (parallel/topology.py HostTopology._unravel / numpy unravel_index).
std::vector<int> unravel(int i, const std::vector<int>& dims) {
  std::vector<int> coords(dims.size(), 0);
  for (size_t k = dims.size(); k-- > 0;) {
    coords[k] = i % dims[k];
    i /= dims[k];
  }
  return coords;
}

std::string json_escape(const std::string& s) {
  std::string out;
  for (char ch : s) {
    if (ch == '"' || ch == '\\') { out += '\\'; out += ch; }
    else if (static_cast<unsigned char>(ch) < 0x20) { out += ' '; }
    else out += ch;
  }
  return out;
}

std::string render(const std::vector<Chip>& chips, const std::string& topo,
                   const char* source) {
  std::string j = "{\"chips\": [";
  for (size_t i = 0; i < chips.size(); ++i) {
    const Chip& c = chips[i];
    if (i) j += ", ";
    std::string coords = "[";
    for (size_t k = 0; k < c.coords.size(); ++k) {
      if (k) coords += ", ";
      coords += std::to_string(c.coords[k]);
    }
    coords += "]";
    char buf[512];
    ::snprintf(buf, sizeof(buf),
               "{\"chip_id\": \"%s\", \"index\": %d, \"pci_addr\": \"%s\", "
               "\"coords\": %s, \"total_hbm_bytes\": %llu, "
               "\"hbm_used_bytes\": %llu}",
               json_escape(c.chip_id).c_str(), c.index,
               json_escape(c.pci_addr).c_str(), coords.c_str(),
               (unsigned long long)c.total_hbm,
               (unsigned long long)c.used_hbm);
    j += buf;
  }
  j += "], \"topology\": \"" + json_escape(topo) + "\", \"source\": \"";
  j += source;
  j += "\"}";
  return j;
}

}  // namespace

extern "C" {

const char* tpuinfo_query(void) {
  std::string mock_json = getenv_str("FMA_TPUINFO_MOCK_JSON");
  if (!mock_json.empty()) return ::strdup(mock_json.c_str());

  const char* source = "pci";
  std::vector<Chip> chips;
  std::string topo = getenv_str("FMA_TPUINFO_TOPOLOGY");

  std::string mock_count = getenv_str("FMA_TPUINFO_MOCK_COUNT");
  if (!mock_count.empty()) {
    chips = enumerate_mock(::atoi(mock_count.c_str()));
    source = "mock";
  } else {
    bool vfio_filtered = false;
    chips = enumerate_pci(&vfio_filtered);
    if (vfio_filtered) source = "pci+vfio";
    if (chips.empty()) {
      chips = enumerate_devfs();
      source = "devfs";
    }
    if (chips.empty()) return ::strdup("{\"chips\": [], \"topology\": \"\", \"source\": \"none\"}");
  }

  // Stable ordering (already sorted per source); assign indices and row-major
  // coords over the topology's own dims, matching the Python model's
  // HostTopology._unravel exactly — placement compares these tuples.
  if (topo.empty()) topo = default_topology(chips.size());
  const std::vector<int> dims = parse_dims(topo);
  const std::string usage_dir = getenv_str("FMA_TPUINFO_USAGE_DIR").empty()
                                    ? "/run/fma-tpu/hbm"
                                    : getenv_str("FMA_TPUINFO_USAGE_DIR");
  for (size_t i = 0; i < chips.size(); ++i) {
    chips[i].index = static_cast<int>(i);
    chips[i].coords = unravel(static_cast<int>(i), dims);
    chips[i].used_hbm = usage_for_chip(usage_dir, chips[i].chip_id);
  }
  return ::strdup(render(chips, topo, source).c_str());
}

void tpuinfo_free(void* p) { ::free(p); }

}  // extern "C"
